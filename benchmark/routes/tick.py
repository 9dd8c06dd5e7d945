"""The fused reconcile-and-classify tick: /v1/tick back to back, or every
``period_s`` from ``first_s``, with the stream's ``body``. Records ``tick``
calls.

The check: every pod's verdict and every throttle's used pod count of
every answer, exactly, against the state at the window's start. A mix that
binds while it ticks would need bounds like ``triage``'s: give it a route
of its own."""

import json

PATH = "/v1/tick"


def drive(d, w, stream, state, start, deadline):
    w.repeat(d, "tick", PATH, stream, start, deadline)


def warm_round(d, stream, state):
    d.call("POST", PATH, stream.get("body", {}))


def check(records, ref, say) -> dict:
    want, want_used = ref.schedulable(), ref.used_counts()
    wrong = used_wrong = answers = 0
    judged = {}
    for route, _, _, ok, raw in records:
        if route != "tick" or not ok:
            continue
        answers += 1
        if raw not in judged:  # the same bytes are judged once
            resp = json.loads(raw)
            sched = resp.get("schedulable", {})
            used = {**resp.get("used", {}).get("throttle", {}),
                    **resp.get("used", {}).get("clusterthrottle", {})}
            judged[raw] = (
                len(resp.get("errors", [])) + sum(sched.get(k) != v for k, v in want.items())
                + len(set(sched) - set(want)),
                sum(used.get(k) != v for k, v in want_used.items()) + len(set(used) - set(want_used)))
        wrong += judged[raw][0]
        used_wrong += judged[raw][1]
    say(f"check: {answers} tick answers ({len(judged)} distinct) against the reference")
    return {"tick_wrong": (wrong, 0), "tick_used_wrong": (used_wrong, 0)}
