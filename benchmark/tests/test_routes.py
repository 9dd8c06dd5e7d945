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
    return binds_window()


def binds_window():
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


W0 = R.instant_ns("2026-03-01T00:00:00Z")


def on_clock(ref, start, end):
    """The window [start, end] in seconds from W0 on the wall clock."""
    ref.wall_ns, ref.window = (lambda t: W0 + int(t * 1e9)), (start, end)
    return ref


@pytest.fixture
def calendar():
    """The window's state, with an override of t to cpu 800m from 5 s to
    15 s: a (300m) fits under the spec's cpu 1 and not under the override."""
    ref, _ = binds_window()
    thr = ref.throttles["default/t"]
    thr["spec"]["temporaryThresholdOverrides"] = [
        {"begin": "2026-03-01T00:00:05Z", "end": "2026-03-01T00:00:15Z",
         "threshold": {"resourceRequests": {"cpu": "800m"}}}]
    return cluster([NS], list(ref.throttles.values()), list(ref.pods.values()))


def tick(t0, t1, **sched):
    used = {"throttle": {"default/t": 1}, "clusterthrottle": {}}
    return ("tick", t0, t1, True, json.dumps(dict(json.loads(answer(**sched)), used=used)).encode())


def tick_check(ref, records):
    lines = []
    out = route("tick").check(records, on_clock(ref, 0.0, 30.0), lines.append)
    return out["tick_wrong"][0], lines[-1]


def test_tick_judges_each_answer_at_its_instant(calendar):
    assert calendar.bounds["default/t"] == [W0 + 5 * R.NANO, W0 + 15 * R.NANO + 1]
    # before the override, on it, and after it: each exactly
    assert tick_check(calendar, [tick(1.0, 2.0), tick(6.0, 7.0, a=False),
                                 tick(15.5, 16.5)])[0] == 0
    assert tick_check(calendar, [tick(1.0, 2.0, a=False)])[0] == 1
    # an answer that ignores the override is wrong while it holds
    assert tick_check(calendar, [tick(6.0, 7.0)])[0] == 1
    assert tick_check(calendar, [tick(14.0, 15.0 - 0.002)])[0] == 1
    # a call across a boundary may answer either side of it, and says so
    for t0, t1 in ((4.5, 5.5), (14.5, 15.5), (4.9995, 5.0)):
        for v in (True, False):
            wrong, line = tick_check(calendar, [tick(t0, t1, a=v)])
            assert wrong == 0 and "1 pod verdicts left open" in line, (t0, t1, v)
    # the pods the boundary does not touch stay exact
    assert tick_check(calendar, [tick(4.5, 5.5, c=False)])[0] == 1


def test_tick_without_overrides_needs_no_clock(window):
    ref, _ = window
    lines = []
    out = route("tick").check([tick(1.0, 2.0), tick(3.0, 4.0, b=False)], ref, lines.append)
    assert out["tick_wrong"] == (1, 0) and out["tick_used_wrong"] == (0, 0)
    assert "0 pod verdicts left open" in lines[-1]


@pytest.mark.parametrize("name", ["triage", "cycle"])
def test_status_routes_refuse_a_boundary_near_the_window(calendar, name):
    # 5 s lies inside the window, and 15 s + 1 ns within RECONCILE_S before it
    for start, end in ((0.0, 10.0), (19.0, 30.0)):
        with pytest.raises(R.RunError, match=f"^{name}: "):
            route(name).check([], on_clock(calendar, start, end), lambda _: None)


def test_status_routes_judge_at_the_window_start(calendar):
    """Clear of every boundary, triage judges at the window's start: a does
    not fit while the override holds, and fits after it."""
    on_clock(calendar, 10.5, 14.0)
    assert triage_wrong(calendar, [("triage", 11.0, 12.0, True, answer(a=False))]) == 0
    assert triage_wrong(calendar, [("triage", 11.0, 12.0, True, answer())]) == 1
    on_clock(calendar, 21.0, 30.0)
    assert triage_wrong(calendar, [("triage", 22.0, 23.0, True, answer())]) == 0
    assert route("cycle").check([], calendar, lambda _: None) == {"prefilter_wrong": (0, 0)}
