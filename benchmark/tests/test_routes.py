"""The routes' checks on hand-made windows: a scan that overlaps binds is
held between the reference before and after them, and nothing wider."""

import json
import os

import pytest

import reference as R
import run as harness

from test_reference import NS, cluster, pod, throttle

ROUTES = os.path.join(harness.BENCH, "routes")


def route(name):
    return harness.load_module(os.path.join(ROUTES, f"{name}.py"), f"route_{name}")


@pytest.fixture
def window():
    """Throttle t (cpu 1) holds 600m; pending a (300m) and b (200m): after
    a's bind neither a nor b fits (a pod's own request counts again), and
    the unthrottled c fits throughout."""
    pods = [pod("x", {"cpu": "600m"}, {"g": "1"}),
            pod("a", {"cpu": "300m"}, {"g": "1"}, node=None, phase="Pending"),
            pod("b", {"cpu": "200m"}, {"g": "1"}, node=None, phase="Pending"),
            pod("c", {"cpu": "100m"}, {"g": "2"}, node=None, phase="Pending")]
    thr = [throttle("t", {"resourceRequests": {"cpu": "1"}},
                    [{"podSelector": {"matchLabels": {"g": "1"}}}])]
    ref = cluster([NS], thr, pods)
    return ref, [("reserve", 10.0, 10.1, True, ("default/a", b"{}")),
                 ("bind", 10.2, 10.3, True, ("default/a", b"{}"))]


def answer(**sched):
    full = {"default/x": False, "default/a": True, "default/b": True, "default/c": True}
    full.update({f"default/{k}": v for k, v in sched.items()})
    return json.dumps({"schedulable": full, "errors": []}).encode()


def triage_wrong(ref, records):
    return route("triage").check(sorted(records, key=lambda r: r[1]), ref, lambda _: None)[
        "triage_wrong"][0]


def test_scan_before_during_and_after_a_bind(window):
    ref, binds = window
    before = ("triage", 5.0, 6.0, True, answer())
    # b's verdict is open while a's reserve and bind are in flight
    during = [("triage", 9.9, 10.5, True, answer(a=v, b=v)) for v in (True, False)]
    after = ("triage", 20.0, 21.0, True, answer(a=False, b=False))
    assert triage_wrong(ref, binds + [before, after] + during) == 0
    # once a's bind has finished, b no longer fits: its verdict is decided
    assert triage_wrong(ref, binds + [("triage", 20.0, 21.0, True, answer(a=False))]) == 1
    # and before a was reserved, b fits
    assert triage_wrong(ref, binds + [("triage", 5.0, 6.0, True, answer(b=False))]) == 1
    # c is unaffected by the bind, and a missing pod is wrong
    assert triage_wrong(ref, binds + [("triage", 9.9, 10.5, True, answer(c=False))]) == 1
    gone = json.loads(answer())
    del gone["schedulable"]["default/c"]
    assert triage_wrong(ref, binds + [("triage", 5.0, 6.0, True, json.dumps(gone).encode())]) == 1
    assert ref.used == cluster([NS], [throttle("t", {"resourceRequests": {"cpu": "1"}}, [
        {"podSelector": {"matchLabels": {"g": "1"}}}])], [pod("x", {"cpu": "600m"}, {"g": "1"})]).used


def test_static_window_is_exact(window):
    ref, _ = window
    assert triage_wrong(ref, [("triage", 1.0, 2.0, True, answer())]) == 0
    assert triage_wrong(ref, [("triage", 1.0, 2.0, True, answer(b=False))]) == 1
    assert R.RECONCILE_S > 0
