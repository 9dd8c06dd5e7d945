"""Profile the reconcile drain (cfg5's event→status path). Run:
    python tools/profile_reconcile.py [P] [T] [EVENTS]
Fires pod-churn events with workers stopped, then cProfiles the
synchronous drain — the per-batch cost that sets status-commit lag.
"""
import cProfile
import io
import os
import pstats
import random
import sys
import time
from dataclasses import replace as dc_replace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kube_throttler_tpu.scenarios.measure import build_served_stack  # noqa: E402
from kube_throttler_tpu.api.pod import make_pod  # noqa: E402

P = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000
T = int(sys.argv[2]) if len(sys.argv) > 2 else 200
EVENTS = int(sys.argv[3]) if len(sys.argv) > 3 else 2_000

store, plugin = build_served_stack(P, T, label="prof")

rng = random.Random(1)
pods = store.list_pods()

def fire(n):
    for i in range(n):
        pod = pods[rng.randrange(len(pods))]
        updated = make_pod(
            pod.name, labels=pod.labels,
            requests={"cpu": f"{rng.randrange(1, 8) * 100}m"},
        )
        updated = dc_replace(updated, spec=dc_replace(updated.spec, node_name="node-1"))
        updated.status.phase = "Running"
        store.update_pod(updated)

# warm the drain path
fire(200)
plugin.run_pending_once()

t0 = time.perf_counter()
fire(EVENTS)
t_fire = time.perf_counter() - t0
print(f"fired {EVENTS} events in {t_fire:.2f}s ({EVENTS/t_fire:,.0f}/s ingest)")

pr = cProfile.Profile()
pr.enable()
t0 = time.perf_counter()
n = plugin.run_pending_once()
t_drain = time.perf_counter() - t0
pr.disable()
print(f"drained {n} keys in {t_drain:.2f}s ({n/t_drain:,.0f} keys/s)")
s = io.StringIO()
pstats.Stats(pr, stream=s).sort_stats("cumulative").print_stats(35)
print(s.getvalue())
