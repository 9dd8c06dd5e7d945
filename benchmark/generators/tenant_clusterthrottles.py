"""Multi-tenant ClusterThrottles: team quotas across tenant namespaces.

After everpeace/kube-throttler's README ClusterThrottle example (a
namespaceSelector AND a podSelector in each of OR-ed ``selectorTerms``,
usage aggregated across every matched namespace) and BASELINE cfg3.
Namespaces carry ``team`` and ``tier`` labels; pods carry ``app``,
``team`` and ``workload``. Each pod's cpu and memory are its own, as a
vertical autoscaler sizes them, and a share of pods asks for GPUs.

A ClusterThrottle quotas a few (team, app) slices, or a team's ML pods in
one tier, in one dimension class. Its threshold is set from what its
selector matches in this draw, with an offset and a factor the seed draws
for it: some quotas are full, some have room for a pod or a few, most have
plenty, so verdicts mix. The seed draws the labels, the selectors and the
thresholds; every seed spreads the same multiset of requests over the pods,
so the sizes do not move.
"""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import reference  # noqa: E402 — benchmark code, beside this directory


def _spread(rng, lo, hi, step, n):
    """n values evenly over [lo, hi] in ``step`` units, shuffled."""
    span = (hi - lo) // step + 1
    vals = [lo + ((i * 7919) % span) * step for i in range(n)]
    rng.shuffle(vals)
    return vals


def generate(cfg: dict, traffic: dict, seed: int) -> dict:
    rng = random.Random(seed)
    n_ns, teams, tiers = cfg["namespaces"], cfg["teams"], cfg["tiers"]
    apps = cfg["apps_per_team"]
    throttler, scheduler = cfg["throttler_name"], cfg["scheduler_name"]
    namespaces = [
        {"kind": "Namespace", "metadata": {"name": f"tenant-{j:03d}", "labels": {
            "team": f"team-{j % teams:02d}", "tier": tiers[(j // teams) % len(tiers)]}}}
        for j in range(n_ns)
    ]
    n = cfg["pods"]
    cpu = _spread(rng, *cfg["cpu_millis"], n)
    mem = _spread(rng, *cfg["memory_mi"], n)
    gpu_every = round(1 / cfg["gpu_share"])
    gpus = _spread(rng, *cfg["gpus"], n)
    ns_of = [i % n_ns for i in range(n)]
    rng.shuffle(ns_of)
    pods = []
    for i in range(n):
        ns = namespaces[ns_of[i]]["metadata"]
        ml = i % gpu_every == 0
        requests = {"cpu": f"{cpu[i]}m", "memory": f"{mem[i]}Mi"}
        if ml:
            requests["nvidia.com/gpu"] = str(gpus[i])
        pods.append({
            "kind": "Pod",
            "metadata": {"name": f"p{i}", "namespace": ns["name"], "labels": {
                "app": f"app-{rng.randrange(apps)}", "team": ns["labels"]["team"],
                "workload": "ml" if ml else ("web", "batch")[i % 2]}},
            "spec": {"schedulerName": scheduler, "nodeName": f"node-{i % cfg['nodes']}",
                     "containers": [{"name": "c", "resources": {"requests": requests}}]},
            "status": {"phase": "Running"},
        })

    throttles = []
    for c in range(cfg["cluster_throttles"]):
        cls = c % 4
        terms = []
        for t in range(cfg["terms"][c % len(cfg["terms"])]):
            team = f"team-{rng.randrange(teams):02d}"
            if cls == 3:  # a team's GPU pods in one tier
                terms.append({
                    "namespaceSelector": {"matchLabels": {"team": team},
                                          "matchExpressions": [{"key": "tier", "operator": "In",
                                                                "values": [tiers[t % len(tiers)]]}]},
                    "podSelector": {"matchLabels": {"workload": "ml"}}})
            else:
                terms.append({
                    "namespaceSelector": {"matchLabels": {"team": team}},
                    "podSelector": {"matchExpressions": [{"key": "app", "operator": "In",
                                                          "values": [f"app-{rng.randrange(apps)}"]}]}})
        throttles.append({"kind": "ClusterThrottle", "metadata": {"name": f"ct{c}"},
                          "spec": {"throttlerName": throttler,
                                   "selector": {"selectorTerms": terms}, "threshold": {}}})

    # thresholds from what each selector matches in this draw
    cluster = reference.Cluster(namespaces, throttles, pods, throttler, scheduler)
    offsets, factors = cfg["count_offsets"], cfg["request_factors"]
    for c, thr in enumerate(throttles):
        count, req = cluster.used[reference.throttle_key(thr)]
        count = count or 0
        off, fac = rng.choice(offsets), rng.choice(factors)
        cpu_m = round(req.get("cpu", 0) * fac / 10**6)
        mem_mi = round(req.get("memory", 0) * fac / (2**20 * 10**9))
        cls = c % 4
        if cls == 0:
            thr["spec"]["threshold"] = {"resourceCounts": {"pod": count + off}}
        elif cls == 1:
            thr["spec"]["threshold"] = {"resourceRequests": {"cpu": f"{max(cpu_m, 1)}m"}}
        elif cls == 2:
            thr["spec"]["threshold"] = {"resourceRequests": {"cpu": f"{max(cpu_m, 1)}m",
                                                             "memory": f"{max(mem_mi, 1)}Mi"}}
        else:
            gpu = req.get("nvidia.com/gpu", 0) // 10**9
            thr["spec"]["threshold"] = {"resourceCounts": {"pod": count + off},
                                        "resourceRequests": {"nvidia.com/gpu": str(gpu + off)}}
    return {"namespaces": namespaces, "throttles": throttles, "pods": pods, "pending": []}
