"""Namespaced Throttles over label groups: the served-stack state.

Copied in structure from ``kube_throttler_tpu.scenarios.measure.served_state``
(bound Running pods in ``grp`` label groups, ``throttles / groups`` Throttles
per group in three classes: open, cpu, pod count), so that program PRs
cannot move the yardstick. What differs is where the thresholds sit: there
every pod-count threshold (5-54) lies far below a group's ~200 pods and
every pending pod is blocked; here each group's thresholds are set from its
own size, so some groups are full, some have room for a few pods, and
verdicts mix.

The seed draws every pod's cpu request from ``cpu_request_millis``, and
each group's cpu factor and pod-count offset from ``cpu_factors`` and
``count_offsets``: every seed is another problem. What it does not change
are the sizes: pods, pending pods and Throttles per group, and so the
number of calls a scan or a tick answers.
"""

import random


def _pod(name, ns, group, cpu_m, scheduler, node):
    pod = {
        "kind": "Pod",
        "metadata": {"name": name, "namespace": ns, "labels": {"grp": f"g{group}"}},
        "spec": {
            "schedulerName": scheduler,
            "containers": [{"name": "c", "resources": {"requests": {"cpu": f"{cpu_m}m"}}}],
        },
        "status": {"phase": "Running" if node else "Pending"},
    }
    if node:
        pod["spec"]["nodeName"] = node
    return pod


def _throttle(i, ns, group, threshold, throttler):
    return {
        "kind": "Throttle",
        "metadata": {"name": f"t{i}", "namespace": ns},
        "spec": {
            "throttlerName": throttler,
            "selector": {"selectorTerms": [{"podSelector": {"matchLabels": {"grp": f"g{group}"}}}]},
            "threshold": threshold,
        },
    }


def generate(cfg: dict, traffic: dict, seed: int) -> dict:
    rng = random.Random(seed)
    ns, groups = cfg["namespace"], cfg["groups"]
    throttler, scheduler = cfg["throttler_name"], cfg["scheduler_name"]
    per_group, per_group_pending = cfg["pods"] // groups, traffic.get("pending_pods", 0) // groups
    choices, nodes = cfg["cpu_request_millis"], cfg["nodes"]
    bound = [(g, rng.choice(choices)) for g in range(groups) for _ in range(per_group)]
    backlog = [(g, rng.choice(choices)) for g in range(groups) for _ in range(per_group_pending)]
    group_m = [0] * groups
    for g, cpu in bound:
        group_m[g] += cpu
    factor = [rng.choice(cfg["cpu_factors"]) for _ in range(groups)]
    offset = [rng.choice(cfg["count_offsets"]) for _ in range(groups)]
    rng.shuffle(bound)
    rng.shuffle(backlog)
    pods = [_pod(f"p{i}", ns, g, cpu, scheduler, f"node-{i % nodes}")
            for i, (g, cpu) in enumerate(bound)]
    pending = [_pod(f"q{i}", ns, g, cpu, scheduler, "") for i, (g, cpu) in enumerate(backlog)]

    throttles = []
    for i in range(cfg["throttles"]):
        g, k = i % groups, i // groups
        cls, step = k % 3, k // 3
        if cls == 0:
            threshold = cfg["open_threshold"]
        elif cls == 1:
            cpu_m = group_m[g] * factor[g] * (1 + cfg["cpu_spread"] * step)
            threshold = {"resourceRequests": {"cpu": f"{round(cpu_m / 100) * 100}m"}}
        else:
            threshold = {"resourceCounts": {"pod": per_group + offset[g] + cfg["count_spread"] * step}}
        throttles.append(_throttle(i, ns, g, threshold, throttler))
    namespaces = [{"kind": "Namespace", "metadata": {"name": ns}}]
    return {"namespaces": namespaces, "throttles": throttles, "pods": pods + pending,
            "pending": [f"{ns}/{p['metadata']['name']}" for p in pending]}
