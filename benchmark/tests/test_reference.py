"""The plain reference on hand cases, and against the program's own oracle
(``api/types.py``) on random amounts and on small seeded states."""

import json
import random
from fractions import Fraction

import pytest

import reference as R

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


@pytest.mark.parametrize("gen,cfg", [("grouped_throttles", "tiny-large"),
                                     ("tenant_clusterthrottles", "tiny-tenant")])
def test_seeded_states_match_program_oracle(gen, cfg):
    """Every matched (pod, throttle) state of a small seeded state, as the
    program's ``api/types.py`` objects compute it."""
    import importlib
    import os

    from kube_throttler_tpu.api.serialization import object_from_dict
    from kube_throttler_tpu.api.types import ResourceAmount, ThrottleStatus, resource_amount_of_pod

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", f"{cfg}.json")) as f:
        config = json.load(f)
    config.update(pods=min(config["pods"], 400))
    state = importlib.import_module(gen).generate(config, {"pending_pods": 50}, 2**31 + 7)
    ref = cluster(state["namespaces"], state["throttles"], state["pods"])
    nss = {n["metadata"]["name"]: object_from_dict(n) for n in state["namespaces"]}
    pods = [object_from_dict(p) for p in state["pods"]]
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
        calc = thr.spec.calculate_threshold(None)
        thr = thr.with_status(ThrottleStatus(calculated_threshold=calc, used=used,
                                             throttled=calc.threshold.is_throttled(used, True)))
        for p in matched:
            want = thr.check_throttled_for(p, ResourceAmount(), False)
            assert ref.status_of(thr.key, p.key) == want, (thr.key, p.key)
