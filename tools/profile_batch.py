"""Profile pre_filter_batch phases (VERDICT r3 task 3). Run:
    python tools/profile_batch.py [P] [T]
"""
import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kube_throttler_tpu.scenarios.measure import build_served_stack  # noqa: E402

P = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000
T = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000

store, plugin = build_served_stack(P, T, label="prof")

plugin.pre_filter_batch()  # warm/compile

t0 = time.perf_counter()
out = plugin.pre_filter_batch()
print(f"warm pre_filter_batch: {(time.perf_counter()-t0)*1e3:.1f}ms "
      f"for {len(out['schedulable'])} pods")

pr = cProfile.Profile()
pr.enable()
plugin.pre_filter_batch()
pr.disable()
s = io.StringIO()
pstats.Stats(pr, stream=s).sort_stats("cumulative").print_stats(30)
print(s.getvalue())
