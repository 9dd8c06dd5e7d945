"""The plain reference on hand cases, and against the program's own oracle
(``api/types.py``) on random amounts, on random override calendars and on
small seeded states."""

import json
import random
import time
from datetime import datetime, timedelta, timezone
from fractions import Fraction

import pytest

import reference as R
import rehearsal

NS = {"kind": "Namespace", "metadata": {"name": "default"}}


def pod(name, requests, labels=None, ns="default", node="n1", phase="Running",
        scheduler="my-scheduler", init=None, overhead=None):
    spec = {"schedulerName": scheduler,
            "containers": [{"name": "c", "resources": {"requests": requests}}]}
    if node:
        spec["nodeName"] = node
    if init:
        spec["initContainers"] = [{"name": "i", "resources": {"requests": init}}]
    if overhead:
        spec["overhead"] = overhead
    return {"kind": "Pod", "metadata": {"name": name, "namespace": ns, "labels": labels or {}},
            "spec": spec, "status": {"phase": phase}}


def throttle(name, threshold, terms, kind="Throttle", ns="default"):
    meta = {"name": name} if kind == "ClusterThrottle" else {"name": name, "namespace": ns}
    return {"kind": kind, "metadata": meta, "spec": {
        "throttlerName": "kube-throttler", "threshold": threshold,
        "selector": {"selectorTerms": terms}}}


def cluster(namespaces, throttles, pods):
    return R.Cluster(namespaces, throttles, pods, "kube-throttler", "my-scheduler")


def test_quantities():
    assert R.quantity("250m") == 250 * 10**6
    assert R.quantity("2") == 2 * 10**9
    assert R.quantity("1.5Gi") == 3 * 2**29 * 10**9
    assert R.quantity("1e3") == 10**12
    assert R.quantity(4) == 4 * 10**9
    assert R.quantity("1n") == 1
    assert R.quantity("0.5n") == Fraction(1, 2)


def test_requests_sum_containers_max_init_plus_overhead():
    p = pod("p", {"cpu": "1"}, init={"cpu": "3", "memory": "1Gi"}, overhead={"cpu": "100m"})
    p["spec"]["containers"].append({"name": "d", "resources": {"requests": {"cpu": "1"}}})
    assert R.pod_requests(p) == {"cpu": R.quantity("3100m"), "memory": R.quantity("1Gi")}


def test_four_states_and_on_equal():
    # the README walkthrough: threshold cpu 200m, pod count 3
    thr = throttle("t1", {"resourceCounts": {"pod": 3}, "resourceRequests": {"cpu": "200m"}},
                   [{"podSelector": {"matchLabels": {"throttle": "t1"}}}])
    lab = {"throttle": "t1"}
    c = cluster([NS], [thr], [pod("a", {"cpu": "100m"}, lab),
                              pod("big", {"cpu": "300m"}, lab, node=""),
                              pod("fits", {"cpu": "100m"}, lab, node=""),
                              pod("over", {"cpu": "150m"}, lab, node="")])
    assert c.status_of("default/t1", "default/big") == R.EXCEEDS
    # 100m used + 100m = 200m: fits exactly (is_throttled_on_equal false)
    assert c.status_of("default/t1", "default/fits") == R.NOT_THROTTLED
    assert c.status_of("default/t1", "default/over") == R.INSUFFICIENT
    c.bind("default/fits")
    # used reaches the threshold: active (flags and step 3 on equal)
    assert c.status_of("default/t1", "default/over") == R.ACTIVE
    assert c.verdict("default/over") == {"default/t1": R.ACTIVE}


def test_request_flags_block_only_pods_that_request_them():
    thr = throttle("t", {"resourceRequests": {"nvidia.com/gpu": "1"}},
                   [{"podSelector": {}}])
    c = cluster([NS], [thr], [pod("g", {"nvidia.com/gpu": "1"}),
                              pod("cpu", {"cpu": "1"}, node=""),
                              pod("g2", {"nvidia.com/gpu": "1"}, node="")])
    assert c.status_of("default/t", "default/cpu") == R.NOT_THROTTLED
    assert c.status_of("default/t", "default/g2") == R.ACTIVE


def test_cluster_throttle_or_terms_namespace_selectors():
    nss = [{"kind": "Namespace", "metadata": {"name": n, "labels": l}} for n, l in
           (("a", {"team": "x"}), ("b", {"team": "y"}), ("c", {"team": "x", "tier": "dev"}))]
    ct = throttle("ct", {"resourceCounts": {"pod": 2}}, [
        {"namespaceSelector": {"matchLabels": {"team": "x"}},
         "podSelector": {"matchExpressions": [{"key": "app", "operator": "In", "values": ["w"]}]}},
        {"namespaceSelector": {"matchLabels": {"team": "y"}},
         "podSelector": {"matchExpressions": [{"key": "app", "operator": "DoesNotExist"}]}},
    ], kind="ClusterThrottle")
    pods = [pod("p1", {"cpu": "1"}, {"app": "w"}, ns="a"),
            pod("p2", {"cpu": "1"}, {"app": "v"}, ns="a"),
            pod("p3", {"cpu": "1"}, {}, ns="b"),
            pod("p4", {"cpu": "1"}, {"app": "w"}, ns="b"),
            pod("p5", {"cpu": "1"}, {"app": "w"}, ns="c", node="")]
    c = cluster(nss, [ct], pods)
    assert c.matched["/ct"] == ["a/p1", "b/p3", "c/p5"]
    assert c.used["/ct"][0] == 2  # p5 is pending
    # ClusterThrottle step 3 is strict under PreFilter; the flag (on equal) blocks
    assert c.status_of("/ct", "c/p5") == R.ACTIVE


def _random_amount(rng):
    count = rng.choice([None, rng.randrange(0, 4)])
    req = rng.choice([None, {k: Fraction(rng.randrange(0, 4)) for k in
                             rng.sample(["cpu", "memory", "gpu"], rng.randrange(0, 3))}])
    return count, req


@pytest.mark.parametrize("seed", range(3))
def test_check_matches_program_oracle(seed):
    from kube_throttler_tpu.api.pod import make_pod
    from kube_throttler_tpu.api.types import (ResourceAmount, Throttle, ThrottleSpec,
                                              ThrottleStatus, ClusterThrottle, ClusterThrottleSpec)

    rng = random.Random(seed)
    for _ in range(3000):
        thr, used, res = _random_amount(rng), _random_amount(rng), _random_amount(rng)
        req = {k: rng.randrange(0, 3) for k in rng.sample(["cpu", "memory", "gpu"], 2)}
        to_ra = lambda a: ResourceAmount(a[0], None if a[1] is None else dict(a[1]))
        p = make_pod("p", requests=req)
        used_ra = to_ra(used)
        status = ThrottleStatus(used=used_ra,
                                throttled=to_ra(thr).is_throttled(used_ra, True))
        for kind in ("Throttle", "ClusterThrottle"):
            obj = (Throttle("t", spec=ThrottleSpec(threshold=to_ra(thr)), status=status)
                   if kind == "Throttle" else
                   ClusterThrottle("t", spec=ClusterThrottleSpec(threshold=to_ra(thr)), status=status))
            want = obj.check_throttled_for(p, to_ra(res), False)
            got = R.check(thr, used, res, {k: Fraction(v) for k, v in req.items()},
                          on_equal=False, step3_on_equal=kind == "Throttle")
            assert got == want, (kind, thr, used, res, req)


EPOCH = datetime(1970, 1, 1, tzinfo=timezone.utc)
T0 = R.instant_ns("2026-03-01T00:00:00Z")
UNPARSEABLE = ["not-a-time", "2026-02-30T00:00:00Z", "2026-03-01T24:00:00Z",
               "2026-03-01 00:00:00Z", "2026-03-01T00:00:00", "2026-03-01T00:00:00+0900"]


class At:
    """An instant in ns that the program's datetimes compare with, so that
    its ``calculate_threshold`` is asked at a nanosecond, where a datetime
    holds microseconds."""

    def __init__(self, ns: int):
        self.ns = ns

    @staticmethod
    def _ns(dt) -> int:
        return (dt - EPOCH) // timedelta(microseconds=1) * 1000

    def __le__(self, dt):
        return self.ns <= self._ns(dt)

    def __ge__(self, dt):
        return self.ns >= self._ns(dt)


def stamp(ns: int, offset_min: int = 0) -> str:
    """RFC3339 for a whole-microsecond instant, in a zone ``offset_min``
    east of UTC."""
    tz = timezone(timedelta(minutes=offset_min))
    t = (EPOCH + timedelta(microseconds=ns // 1000)).astimezone(tz)
    frac = f".{t.microsecond:06d}" if t.microsecond else ""
    zone = "Z" if not offset_min else t.strftime("%z")[:3] + ":" + t.strftime("%z")[3:]
    return t.strftime("%Y-%m-%dT%H:%M:%S") + frac + zone


def as_reference(amount):
    """A program ``ResourceAmount`` as the reference's (count, requests),
    an absent requests map as an empty one."""
    return (amount.resource_counts,
            {k: v * R.NANO for k, v in (amount.resource_requests or {}).items()})


def _random_threshold(rng):
    out = {}
    if rng.random() < 0.6:
        out["resourceCounts"] = {"pod": rng.randrange(0, 5)}
    if rng.random() < 0.6:
        out["resourceRequests"] = {k: f"{rng.randrange(0, 4000)}m"
                                   for k in rng.sample(["cpu", "memory", "gpu"], rng.randrange(1, 3))}
    return out


def _random_overrides(rng):
    """0-4 overrides on a grid of whole seconds (some with microseconds, some
    in another zone): overlapping windows, open ends, unparseable strings,
    count-only and request-only thresholds, begin == end."""
    def end():
        r = rng.random()
        if r < 0.2:
            return ""
        if r < 0.27:
            return rng.choice(UNPARSEABLE)
        ns = T0 + rng.randrange(8) * R.NANO + rng.choice([0, 0, rng.randrange(1, 10**6) * 1000])
        return stamp(ns, rng.choice([0, 0, 540, -300]))

    out = []
    for _ in range(rng.randrange(0, 5)):
        o = {"begin": end(), "threshold": _random_threshold(rng)}
        o["end"] = o["begin"] if rng.random() < 0.1 else end()
        out.append({k: v for k, v in o.items() if v != "" or rng.random() < 0.5})
    return out


@pytest.mark.parametrize("seed", range(3))
def test_overrides_match_program_oracle(seed):
    """``calculate_threshold`` of ``api/types.py`` against the reference's
    resolution, by its function and by a ``Cluster``'s boundaries, at
    random instants and on, 1 ns before and 1 ns after every begin and end."""
    from kube_throttler_tpu.api.serialization import object_from_dict

    rng = random.Random(seed)
    compared = edges = 0
    for _ in range(3000):
        thr_d = throttle("t", _random_threshold(rng), [{"podSelector": {}}])
        thr_d["spec"]["temporaryThresholdOverrides"] = _random_overrides(rng)
        spec = object_from_dict(thr_d).spec
        ref = cluster([NS], [thr_d], [])
        ovs = R.overrides_of(thr_d["spec"])
        marks = [x for b, e, _ in ovs for x in (b, e) if x is not None]
        instants = [x + d for x in marks for d in (-1, 0, 1)]
        instants += [T0 + rng.randrange(-R.NANO, 9 * R.NANO) for _ in range(3)]
        for t in instants:
            want = as_reference(spec.calculate_threshold(At(t)).threshold)
            for got in (R.threshold_at(R.amount_of(thr_d["spec"]["threshold"]), ovs, t),
                        ref.threshold_at("default/t", t)):
                assert (got[0], got[1] or {}) == want, (thr_d["spec"], t - T0)
            compared += 1
        edges += len(marks)
    assert compared > 3000 * 3 and edges > 3000


def test_instants_and_boundaries():
    assert R.instant_ns("1970-01-01T00:00:00Z") == 0
    assert R.instant_ns("2024-02-29T23:59:59.123456789123+09:00") == (
        R.instant_ns("2024-02-29T14:59:59Z") + 123456789)
    assert R.instant_ns("2024-01-01T00:00:00-05:30") == R.instant_ns("2024-01-01T05:30:00Z")
    for bad in UNPARSEABLE + ["2023-02-29T00:00:00Z", "2024-01-01T00:00:60Z", ""]:
        assert R.instant_ns(bad) is None, bad
    one = (1, None)
    ovs = R.overrides_of({"temporaryThresholdOverrides": [
        {"begin": "1970-01-01T00:00:02Z", "end": "1970-01-01T00:00:04Z", "threshold": {}},
        {"begin": "bad", "threshold": {"resourceCounts": {"pod": 7}}},
        {"end": "1970-01-01T00:00:03Z", "threshold": {"resourceCounts": {"pod": 5}}}]})
    assert [(b, e) for b, e, _ in ovs] == [(2 * R.NANO, 4 * R.NANO), (None, 3 * R.NANO)]
    assert R.boundaries(ovs) == [2 * R.NANO, 3 * R.NANO + 1, 4 * R.NANO + 1]
    # the first active wins for the count; an override without one leaves it absent
    assert R.threshold_at(one, ovs, 1 * R.NANO) == (5, {})
    assert R.threshold_at(one, ovs, 3 * R.NANO) == (5, {})
    assert R.threshold_at(one, ovs, 3 * R.NANO + 1) == (None, {})
    assert R.threshold_at(one, ovs, 4 * R.NANO + 1) == one


@pytest.mark.parametrize("cfg", [c["name"] for c in rehearsal.configs()])
def test_seeded_states_match_program_oracle(cfg):
    """Every matched (pod, throttle) state of a small seeded state of each
    configuration's generator at its tiny size, as the program's
    ``api/types.py`` objects compute it: at one instant, or where the state
    has overrides, on and 1 ns before up to 64 of its boundaries, chosen by
    the seed."""
    import importlib

    from kube_throttler_tpu.api.serialization import object_from_dict
    from kube_throttler_tpu.api.types import ResourceAmount, ThrottleStatus, resource_amount_of_pod

    seed = 2**31 + 7
    with open(rehearsal.tiny_config(cfg)) as f:
        config = json.load(f)
    config.update(pods=min(config["pods"], 400))
    gen = importlib.import_module(config["generator"])
    state = gen.generate(config, {"pending_pods": 50}, seed)
    ref = cluster(state["namespaces"], state["throttles"], state["pods"])
    nss = {n["metadata"]["name"]: object_from_dict(n) for n in state["namespaces"]}
    pods = [object_from_dict(p) for p in state["pods"]]
    throttles = []
    for thr_d in state["throttles"]:
        thr = object_from_dict(thr_d)
        if thr_d["kind"] == "Throttle":
            matched = [p for p in pods if p.namespace == thr.namespace
                       and thr.spec.selector.matches_to_pod(p)]
        else:
            matched = [p for p in pods if thr.spec.selector.matches_to_pod(p, nss[p.namespace])]
        assert sorted(p.key for p in matched) == ref.matched[thr.key]
        used = ResourceAmount()
        for p in matched:
            if p.spec.node_name:
                used = used.add(resource_amount_of_pod(p))
        throttles.append((thr, matched, used))
    marks = sorted({y for bs in ref.bounds.values() for x in bs for y in (x - 1, x)})
    instants = sorted(random.Random(seed).sample(marks, min(64, len(marks)))) or [time.time_ns()]
    for t in instants:
        ref.resolve(t)
        for thr, matched, used in throttles:
            calc = thr.spec.calculate_threshold(At(t))
            got = ref.thresholds[thr.key]
            assert (got[0], got[1] or {}) == as_reference(calc.threshold), (thr.key, t)
            thr = thr.with_status(ThrottleStatus(calculated_threshold=calc, used=used,
                                                 throttled=calc.threshold.is_throttled(used, True)))
            for p in matched:
                want = thr.check_throttled_for(p, ResourceAmount(), False)
                assert ref.status_of(thr.key, p.key) == want, (thr.key, p.key, t)
