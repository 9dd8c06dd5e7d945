"""The fused reconcile-and-classify tick: /v1/tick back to back, or every
``period_s`` from ``first_s``, with the stream's ``body``. Records ``tick``
calls.

The check: every pod's verdict and every throttle's used pod count of
every answer against the state at the window's start. The tick resolves
time-varying thresholds at an instant of its call, so an answer is judged
over its call's interval on the wall clock: a pod none of whose throttles
has a boundary inside it exactly, at the interval's start; a pod with one
may hold any verdict the reference gives at the start or from any such
boundary on (left open where those differ). A mix that binds while it
ticks would need bounds like ``triage``'s: give it a route of its own."""

import bisect
import json

import reference

PATH = "/v1/tick"
FAULTS = ("altered_answer", "half_batch")


def drive(d, w, stream, state, start, deadline):
    w.repeat(d, "tick", PATH, stream, start, deadline)


def warm_round(d, stream, state):
    d.call("POST", PATH, stream.get("body", {}))


def pods_of(ref, tkeys) -> set:
    return {pk for k in tkeys for pk in ref.matched[k]}


def over_interval(ref, first: dict, want0: dict, a: int, b: int):
    """({pod: verdict}, open pods) of an answer made at some instant of
    [a, b]: the verdicts at a, and the pods whose verdict differs at some
    boundary of theirs inside it. ``first`` and ``want0`` are the
    thresholds and verdicts at the window's start: only the pods of the
    throttles that resolve otherwise at a are recomputed."""
    ref.resolve(a)
    want = dict(want0)
    want.update(ref.schedulable(pods=pods_of(ref, [k for k in ref.bounds
                                                    if ref.thresholds[k] != first[k]])))
    at = {}
    for k, xs in ref.crossing(a, b).items():
        for x in xs:
            at.setdefault(x, []).append(k)
    seen = {}
    for x in sorted(at):  # the thresholds walk from a's to b's, one boundary at a time
        ref.resolve(x, at[x])
        for pk, ok in ref.schedulable(pods=pods_of(ref, at[x])).items():
            seen.setdefault(pk, {want[pk]}).add(ok)
    return want, frozenset(pk for pk, v in seen.items() if len(v) > 1)


def check(records, ref, say) -> dict:
    timed = bool(ref.bounds)
    if timed:
        ref.resolve(ref.wall_ns(ref.window[0]))
    first = dict(ref.thresholds)
    want0, want_used = ref.schedulable(), ref.used_counts()
    wrong = used_wrong = answers = left_open = 0
    expected, judged = {(): (want0, frozenset())}, {}
    for route, t0, t1, ok, raw in records:
        if route != "tick" or not ok:
            continue
        answers += 1
        span = ()
        if timed:
            a = ref.wall_ns(t0) - reference.CLOCK_SLACK_NS
            b = ref.wall_ns(t1) + reference.CLOCK_SLACK_NS
            # the thresholds over [a, b] follow from where a and b fall
            span = tuple((bisect.bisect_right(bs, a), bisect.bisect_right(bs, b))
                         for bs in ref.bounds.values())
            if span not in expected:
                expected[span] = over_interval(ref, first, want0, a, b)
        want, undecided = expected[span]
        left_open += len(undecided)
        if (raw, span) not in judged:  # the same bytes against the same verdicts are judged once
            resp = json.loads(raw)
            sched = resp.get("schedulable", {})
            used = {**resp.get("used", {}).get("throttle", {}),
                    **resp.get("used", {}).get("clusterthrottle", {})}
            bad = len(resp.get("errors", [])) + len(set(sched) - set(want))
            for pk, v in want.items():
                got = sched.get(pk)
                bad += got not in (True, False) or (got != v and pk not in undecided)
            judged[raw, span] = (bad, sum(used.get(k) != v for k, v in want_used.items())
                                 + len(set(used) - set(want_used)))
        wrong += judged[raw, span][0]
        used_wrong += judged[raw, span][1]
    say(f"check: {answers} tick answers ({len(judged)} distinct) against the reference; "
        f"{left_open} pod verdicts left open by threshold boundaries inside their call")
    return {"tick_wrong": (wrong, 0), "tick_used_wrong": (used_wrong, 0)}


def control(ctl, stream, state, calls) -> list:
    """One answer of the window from the reference ``ctl`` in the
    program's place, verdicts and used pod counts, resolved at the
    instant its call starts."""
    if ctl.bounds:
        ctl.resolve(ctl.wall_ns(0.0))
    used = ctl.used_counts()
    answer = {"schedulable": ctl.schedulable(), "errors": [],
              "used": {"throttle": {k: v for k, v in used.items() if not k.startswith("/")},
                       "clusterthrottle": {k: v for k, v in used.items() if k.startswith("/")}}}
    return [("tick", 0.0, 0.5, True, json.dumps(answer).encode())]
