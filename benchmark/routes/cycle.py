"""kube-scheduler's serial cycle over the pending backlog: PreFilter, then
Reserve and Bind on Success; a rejected pod goes to the back of the queue.
Records ``prefilter``, ``reserve`` and ``bind`` calls.

The check: every PreFilter of the window against the reference after the
binds the window made before it. A bound pod counts once, as reserved or as
used; within ``reference.RECONCILE_S`` of its bind it may count twice, so a
state may lie between the two (never below the first). PreFilter reads
written statuses, so a state whose thresholds change near or inside the
window is refused (``reference.Cluster.hold_still``); any other is judged
at the window's start."""

import collections
import json

import reference

FAULTS = ("altered_answer", "state_unchanged")


def drive(d, w, stream, state, start, deadline):
    queue = collections.deque(state["pending"])
    node = 0
    while queue and w.now() < deadline:
        pk = queue.popleft()
        ok, raw = w.timed(d, "prefilter", "/v1/prefilter", {"podKey": pk}, keep=pk)
        if ok and json.loads(raw).get("code") == "Success":
            ok, raw = w.timed(d, "reserve", "/v1/reserve", {"podKey": pk}, keep=pk)
            if ok and json.loads(raw).get("code") == "Success":
                node += 1
                w.timed(d, "bind", "/v1/bind", {"podKey": pk, "nodeName": f"node-{node % 5000}"},
                        keep=pk)
        else:
            queue.append(pk)


def warm_round(d, stream, state):
    """A probe pod that is not in the store through every call of the
    cycle, its reservation undone."""
    first = next(p for p in state["pods"]
                 if reference.pod_key(p) == state["pending"][0])
    probe = json.loads(json.dumps(first))
    probe["metadata"]["name"] = "benchmark-warmup"
    for path in ("/v1/prefilter", "/v1/reserve", "/v1/unreserve", "/v1/prefilter"):
        d.call("POST", path, probe)


def served_map(resp: dict) -> dict:
    """{throttle key: state} from a /v1/prefilter response's reasons."""
    out = {}
    for reason in resp.get("reasons", []):
        head, _, names = reason.partition("=")
        state = head[head.index("[") + 1:head.index("]")]
        for key in names.split(","):
            out[key] = state
    return out


def check(records, ref, say) -> dict:
    ref.hold_still("cycle")
    recent = collections.defaultdict(collections.deque)  # throttle -> (t, amount)
    wrong, above, checked = 0, 0, 0
    for route, t0, t1, ok, payload in records:
        if route not in ("prefilter", "bind") or not ok:
            continue
        pk, raw = payload
        if route == "bind":
            ref.bind(pk)
            for tkey in ref.matching[pk]:
                recent[tkey].append((t1, (1, ref.requests[pk])))
            continue
        resp = json.loads(raw)
        served = served_map(resp)
        checked += 1
        bad = resp.get("code") not in ("Success", "UnschedulableAndUnresolvable") or (
            (resp["code"] == "Success") != (not served)) or not set(served) <= set(ref.matching[pk])
        differs = False
        for tkey in ref.matching[pk]:
            q = recent[tkey]
            while q and q[0][0] < t0 - reference.RECONCILE_S:
                q.popleft()
            extra = reference.ZERO
            for _, amount in q:
                extra = reference.add(extra, amount)
            lo, hi = ref.status_of(tkey, pk), ref.status_of(tkey, pk, extra)
            got = served.get(tkey, reference.NOT_THROTTLED)
            if lo == reference.EXCEEDS or got == reference.EXCEEDS:
                bad |= got != lo
            else:
                bad |= not reference.RANK[lo] <= reference.RANK[got] <= reference.RANK[hi]
                differs |= got != lo
        wrong += bad
        above += differs and not bad
    say(f"check: {checked} PreFilter verdicts; {wrong} outside the reference's bounds; "
        f"{above} above the single-count state (a bind counted twice within "
        f"{reference.RECONCILE_S:g}s)")
    return {"prefilter_wrong": (wrong, 0)}


def served_reasons(verdict: dict) -> list:
    """A /v1/prefilter answer's reasons, in the daemon's format."""
    by = collections.defaultdict(list)
    for key, state in sorted(verdict.items()):
        kind = "throttle" if not key.startswith("/") else "clusterthrottle"
        by[f"{kind}[{state}]"].append(key)
    return [f"{head}={','.join(keys)}" for head, keys in by.items()]


def control(ctl, stream, state, calls) -> list:
    """The window's records with the reference ``ctl`` in the program's
    place: ``calls`` PreFilters of the cycle, binding what it admits."""
    queue = collections.deque(state["pending"])
    records, t = [], 0.0
    for _ in range(calls):
        if not queue:
            break
        pk = queue.popleft()
        verdict = ctl.verdict(pk)
        code = "UnschedulableAndUnresolvable" if verdict else "Success"
        raw = json.dumps({"code": code, "reasons": served_reasons(verdict)}).encode()
        records.append(("prefilter", t, t + 1e-3, True, (pk, raw)))
        if verdict:
            queue.append(pk)
        else:
            ctl.bind(pk)
            records.append(("bind", t + 2e-3, t + 3e-3, True, (pk, b"{}")))
        t += 1e-2
    return records
