"""Whole-cluster triage: /v1/prefilter-batch back to back, or every
``period_s`` from ``first_s``, every stored pod classified per call.
Records ``triage`` calls.

The check: every pod of every answer. Where another stream reserves and
binds pods during the window, which of those an answer saw is not fixed;
but binds only add to used, and a pod's verdict only tightens as used
grows. So each pod's verdict has to lie between the reference with the
binds finished before the call was sent (each counted once) and with every
pod reserved before it returned, plus every bind of the last
``reference.RECONCILE_S`` counted twice. A pod on which the two agree, as
every pod does in a window that binds nothing, is checked exactly. The
answers read written statuses, so a state whose thresholds change near or
inside the window is refused (``reference.Cluster.hold_still``); any other
is judged at the window's start."""

import json

import reference

PATH = "/v1/prefilter-batch"
FAULTS = ("altered_answer", "half_batch")


def drive(d, w, stream, state, start, deadline):
    w.repeat(d, "triage", PATH, stream, start, deadline)


def warm_round(d, stream, state):
    d.call("POST", PATH, stream.get("body", {}))


def notes(calls, phases):
    n = calls.get("triage", 0)
    return [f"route: {n} triage calls, {phases.get('batch_dispatch', 0)} device dispatches, "
            f"{phases.get('prefilter_batch', 0) - phases.get('batch_dispatch', 0)}"
            " answered by the host dedupe"]


def in_flight(records, t0, t1):
    """(once, later, twice): the pods whose binds finished before t0; those
    reserved before t1 and not among them; those whose bind may count
    twice in a reading between t0 and t1."""
    binds = [(b0, b1, p[0]) for r, b0, b1, ok, p in records if r == "bind" and ok]
    once = tuple(pk for _, b1, pk in binds if b1 < t0)
    later = {p[0] for r, r0, _, ok, p in records if r == "reserve" and ok and r0 < t1}
    twice = tuple(pk for b0, b1, pk in binds if b0 < t1 and b1 > t0 - reference.RECONCILE_S)
    return once, tuple(sorted(later - set(once))), twice


def bounds(ref, once, later, twice):
    """(lower, upper) {pod key: schedulable}: ``once`` counted on top of
    the window's starting state, then ``later`` and ``twice`` on top."""
    base = ref.used
    ref.used = dict(base)
    for tkey, amount in ref.amounts(once).items():
        ref.used[tkey] = reference.add(ref.used[tkey], amount)
    try:
        return ref.schedulable(), ref.schedulable(extra=ref.amounts(later + twice))
    finally:
        ref.used = base


def check(records, ref, say) -> dict:
    ref.hold_still("triage")
    wrong, undecided, answers = 0, 0, 0
    states, judged = {}, {}
    for route, t0, t1, ok, raw in records:
        if route != "triage" or not ok:
            continue
        answers += 1
        pods = in_flight(records, t0, t1)
        if pods not in states:
            states[pods] = bounds(ref, *pods)
        lo, hi = states[pods]
        if (raw, pods) not in judged:  # the same bytes against the same bounds are judged once
            resp = json.loads(raw)
            sched, errors = resp.get("schedulable", {}), resp.get("errors", [])
            bad = len(errors) + len(set(sched) - set(lo))
            for pk, want in lo.items():
                got = sched.get(pk)
                bad += got not in (True, False) or (want == hi[pk] and got != want)
            judged[raw, pods] = bad
            undecided += sum(lo[k] != hi[k] for k in lo)
        wrong += judged[raw, pods]
    say(f"check: {answers} triage answers ({len(judged)} distinct) against the reference; "
        f"{undecided} pod verdicts left open by binds in flight")
    return {"triage_wrong": (wrong, 0)}


def control(ctl, stream, state, calls) -> list:
    """One answer of the window from the reference ``ctl`` in the
    program's place."""
    raw = json.dumps({"schedulable": ctl.schedulable(), "errors": []}).encode()
    return [("triage", 0.0, 0.5, True, raw)]
