"""Test bootstrap: force an 8-device virtual CPU mesh before JAX imports.

Multi-chip TPU hardware is not available in CI; sharding tests run against
XLA's host-platform device partitioning instead (same SPMD partitioner the
TPU path uses).
"""

import os

# Runtime lock-order assassin (utils/lockorder.py): on for the whole
# suite so the chaos/soak tiers double as a race detector. Must be set
# before any kube_throttler_tpu import — module- and class-level locks
# are created at import time. Opt out per-run with KT_LOCK_ASSERT=0.
os.environ.setdefault("KT_LOCK_ASSERT", "1")

# Eraser-style lockset race detector (utils/racedetect.py): also armed
# suite-wide — every GUARDED_BY attribute access refines a per-(object,
# attribute) candidate lockset, and pytest_sessionfinish below fails
# the run on any unwaived report. Same import-time constraint as the
# assassin (guard_attrs installs the tracking descriptors at class
# decoration). Opt out per-run with KT_RACE_DETECT=0.
os.environ.setdefault("KT_RACE_DETECT", "1")

# Verdict-coherence assassin (utils/epochassert.py): sampled VerdictCache
# hits are shadow-recomputed through the uncached oracle route; a
# divergence at an unchanged epoch sum proves a mutation skipped its
# epoch bump and raises StaleVerdict at first observation. Same
# import-time constraint (plugin caches the flag at construction).
# Opt out per-run with KT_EPOCH_ASSERT=0.
os.environ.setdefault("KT_EPOCH_ASSERT", "1")

# force, not setdefault: the suite runs on the CPU with 8 virtual devices
# even on a host with a chip; jax.config is updated too, in case a plugin
# imported jax before this file ran
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import re

import jax

jax.config.update("jax_platforms", "cpu")
_want = int(
    re.search(
        r"xla_force_host_platform_device_count=(\d+)", os.environ["XLA_FLAGS"]
    ).group(1)
)
assert jax.local_device_count() == _want, (
    f"expected {_want} virtual CPU devices, got {jax.devices()}; either a "
    "backend was initialized before conftest could force the CPU platform, "
    "or the ambient XLA_FLAGS device count disagrees (tests need 8)"
)
assert jax.local_device_count() == 8, (
    f"tests assume an 8-device mesh; ambient XLA_FLAGS pinned "
    f"{jax.local_device_count()} — unset xla_force_host_platform_device_count"
)


def pytest_sessionfinish(session, exitstatus):
    """Race-detector gate: any unwaived lockset report fails the run —
    the dynamic twin of the analyzer's exit-1-on-new-finding contract.
    Planted-race fixtures isolate themselves via racedetect.capture(),
    so anything left here came from real code under real tests."""
    from kube_throttler_tpu.utils import racedetect

    if not racedetect.enabled():
        return
    reps = racedetect.reports()
    if reps:
        print(
            "\n=== racedetect: unwaived lockset race(s) — fix, or waive in "
            "kube_throttler_tpu/analysis/race_allow.txt with a justification ==="
        )
        for r in reps:
            print(r.render())
        session.exitstatus = 1


class ProcReader:
    """Deadline-safe stdout scraping for daemon subprocesses: readline()
    has no timeout, so a drain thread feeds a queue and callers poll with
    deadlines. ONE reader per process — competing drain threads steal each
    other's lines."""

    def __init__(self, proc):
        import queue
        import threading

        self.proc = proc
        self.lines: "queue.Queue[str]" = queue.Queue()
        self.seen: list = []
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)

    def wait_for(self, pattern: str, timeout_s: float = 60.0) -> list:
        """Block until a line matches ``pattern`` (regex); returns all lines
        seen so far. Raises AssertionError (with the transcript) at the
        deadline."""
        import queue
        import re
        import time

        deadline = time.time() + timeout_s
        while time.time() < deadline:
            try:
                line = self.lines.get(timeout=0.5)
            except queue.Empty:
                continue
            self.seen.append(line)
            if re.search(pattern, line):
                return list(self.seen)
        raise AssertionError(f"pattern {pattern!r} not seen in {self.seen}")

    def assert_absent(self, pattern: str, during_s: float) -> None:
        """Drain for ``during_s`` asserting no line matches ``pattern``."""
        import queue
        import re
        import time

        deadline = time.time() + during_s
        while time.time() < deadline:
            try:
                line = self.lines.get(timeout=0.2)
            except queue.Empty:
                continue
            self.seen.append(line)
            assert not re.search(pattern, line), (pattern, self.seen)


def normalize_reasons(reasons):
    """Order-insensitive form of PreFilter reason strings: throttle-name
    order within one reason is not part of the contract (set iteration
    differs between the device decode and the host walk). Shared by the
    differential and flaky-device soaks."""
    out = []
    for r in reasons:
        head, _, names = r.partition("=")
        out.append(f"{head}={','.join(sorted(names.split(',')))}")
    return sorted(out)
