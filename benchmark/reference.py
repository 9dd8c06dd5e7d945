"""The plain reference: kube-throttler's admission semantics over manifests.

Imports nothing of ``kube_throttler_tpu`` and takes nothing the program
made: it reads the same Kubernetes manifests (plain dicts) the generator
hands the daemon, and the binds the harness itself made. Written from
everpeace/kube-throttler's Go sources as published:

- a pod's amount is ``{pod: 1}`` plus its effective requests: the sum over
  containers, raised to the largest init container, plus the overhead;
- a Throttle selects pods of its own namespace (OR of terms, each a label
  selector); a ClusterThrottle's term is namespaceSelector AND podSelector;
- ``used`` sums the amounts of matched pods that are bound (nodeName set),
  belong to the governed scheduler and have not finished;
- a dimension is throttled when ``used >= threshold`` (on equal) or
  ``used > threshold``; dimensions absent from the threshold are not
  checked, and absent from ``used`` do not throttle;
- the four ordered states: pod-requests-exceeds-threshold, active (the
  persisted flags, then ``used + reserved`` on step 3's equality rule),
  insufficient (``used + reserved + request``), not-throttled. A request
  flag only blocks a pod that requests that resource non-zero.

Amounts are ``(count, requests)``: ``count`` is None where Go's pointer is
nil, ``requests`` maps resource names to exact quantities in nano-units: an
int, or a ``Fraction`` for the rare quantity finer than that.
"""

import json
from fractions import Fraction
from typing import Dict, List, Optional, Tuple

NOT_THROTTLED = "not-throttled"
ACTIVE = "active"
INSUFFICIENT = "insufficient"
EXCEEDS = "pod-requests-exceeds-threshold"
# the order a growing usage walks a throttle's verdict through
RANK = {NOT_THROTTLED: 0, INSUFFICIENT: 1, ACTIVE: 2}

# a bound pod counts once in a throttle's used + reserved, but twice between
# a reconcile's status write and its release of the reservation: a few ms
# of one commit by the program's design. A reading may count a bind twice
# for this long after it, and no older one
RECONCILE_S = 5.0

NANO = 10**9
_BINARY = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40, "Pi": 2**50, "Ei": 2**60}
_DECIMAL = {"n": Fraction(1, 10**9), "u": Fraction(1, 10**6), "m": Fraction(1, 1000),
            "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12, "P": 10**15, "E": 10**18}


def _exact(s: str) -> Fraction:
    for suffix, mult in _BINARY.items():
        if s.endswith(suffix):
            return Fraction(s[: -len(suffix)]) * mult
    if len(s) > 1 and s[-1] in _DECIMAL and s[-2] not in "eE":
        return Fraction(s[:-1]) * _DECIMAL[s[-1]]
    if "e" in s or "E" in s:
        mant, _, exp = s.replace("E", "e").partition("e")
        return Fraction(mant) * Fraction(10) ** int(exp)
    return Fraction(s)


def quantity(q):
    """A Kubernetes quantity ("250m", "2", "1.5Gi", "1e3", 4) in nano-units,
    exactly: an int where it is whole there, else a Fraction."""
    v = _exact(str(q).strip()) * NANO
    return v.numerator if v.denominator == 1 else v


def pod_requests(pod: dict) -> Dict[str, object]:
    spec = pod.get("spec", {})
    total: Dict[str, object] = {}
    for c in spec.get("containers") or []:
        for name, q in ((c.get("resources") or {}).get("requests") or {}).items():
            total[name] = total.get(name, 0) + quantity(q)
    for c in spec.get("initContainers") or []:
        for name, q in ((c.get("resources") or {}).get("requests") or {}).items():
            if name in total:
                total[name] = max(total[name], quantity(q))
            else:
                total[name] = quantity(q)
    for name, q in (spec.get("overhead") or {}).items():
        total[name] = total.get(name, 0) + quantity(q)
    return total


def amount_of(manifest_amount: Optional[dict]):
    """A threshold from its manifest form {resourceCounts, resourceRequests}."""
    manifest_amount = manifest_amount or {}
    counts = manifest_amount.get("resourceCounts")
    requests = manifest_amount.get("resourceRequests")
    return (
        None if counts is None else int(counts.get("pod", 0)),
        None if requests is None else {k: quantity(v) for k, v in requests.items()},
    )


def add(a, b):
    """Go's ResourceAmount.Add: a nil count stays nil only if both are nil."""
    count = b[0] if a[0] is None else (a[0] if b[0] is None else a[0] + b[0])
    req = dict(a[1] or {})
    for k, v in (b[1] or {}).items():
        req[k] = req.get(k, 0) + v
    return (count, req)


ZERO = (None, {})


def throttled_flags(threshold, used, on_equal: bool):
    """(count flag, {resource: flag} or None) — resource_amount.go IsThrottled."""
    def hit(u, t):
        return u >= t if on_equal else u > t

    count_flag = threshold[0] is not None and used[0] is not None and hit(used[0], threshold[0])
    req_flags = None
    if threshold[1]:
        used_req = used[1] or {}
        req_flags = {k: (k in used_req and hit(used_req[k], t)) for k, t in threshold[1].items()}
    return count_flag, req_flags


def blocks(flags, requests: Dict[str, object]) -> bool:
    count_flag, req_flags = flags
    if count_flag:
        return True
    return any(q != 0 and (req_flags or {}).get(k, False) for k, q in requests.items())


def check(threshold, used, reserved, requests, on_equal: bool, step3_on_equal: bool) -> str:
    """The four ordered states of one throttle for one pod; the persisted
    throttled flags are those a reconcile derives from ``used`` (on equal)."""
    pod = (1, requests)
    if blocks(throttled_flags(threshold, pod, False), requests):
        return EXCEEDS
    if blocks(throttled_flags(threshold, used, True), requests):
        return ACTIVE
    already = add(add(ZERO, used), reserved)
    if blocks(throttled_flags(threshold, already, step3_on_equal), requests):
        return ACTIVE
    if blocks(throttled_flags(threshold, add(already, pod), on_equal), requests):
        return INSUFFICIENT
    return NOT_THROTTLED


def selector_matches(selector: Optional[dict], labels: Dict[str, str]) -> bool:
    """metav1.LabelSelector: AND of matchLabels and matchExpressions."""
    selector = selector or {}
    for k, v in (selector.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for req in selector.get("matchExpressions") or []:
        key, op, values = req["key"], req["operator"], req.get("values") or []
        if op == "In" and (key not in labels or labels[key] not in values):
            return False
        if op == "NotIn" and key in labels and labels[key] in values:
            return False
        if op == "Exists" and key not in labels:
            return False
        if op == "DoesNotExist" and key in labels:
            return False
    return True


def pod_key(pod: dict) -> str:
    return f"{pod['metadata'].get('namespace', 'default')}/{pod['metadata']['name']}"


def throttle_key(thr: dict) -> str:
    if thr["kind"] == "Throttle":
        return f"{thr['metadata'].get('namespace', 'default')}/{thr['metadata']['name']}"
    return f"/{thr['metadata']['name']}"


def _terms(thr: dict) -> list:
    sel = thr["spec"].get("selector") or {}
    return sel.get("selectorTerms") or sel.get("selecterTerms") or []


def _candidates(selector: dict, ns: str, by_label, by_ns) -> List[str]:
    """The pods of ``ns`` that can match ``selector``: those carrying its
    first required label, or one of the values of its first ``In``."""
    for k, v in (selector.get("matchLabels") or {}).items():
        return by_label.get((ns, k, v), [])
    for req in selector.get("matchExpressions") or []:
        if req["operator"] == "In":
            return [pk for v in req.get("values") or [] for pk in by_label.get((ns, req["key"], v), [])]
    return by_ns.get(ns, [])


class Cluster:
    """Namespaces, throttles of both kinds and pods, as manifests, with the
    statuses a reconcile derives from them. ``throttler`` and ``scheduler``
    are the names the daemon governs."""

    def __init__(self, namespaces: List[dict], throttles: List[dict], pods: List[dict],
                 throttler: str, scheduler: str):
        self.scheduler = scheduler
        self.ns_labels = {n["metadata"]["name"]: n["metadata"].get("labels") or {}
                          for n in namespaces}
        self.throttles = {throttle_key(t): t for t in throttles
                          if t["spec"].get("throttlerName") == throttler}
        self.thresholds = {k: amount_of(t["spec"].get("threshold"))
                           for k, t in self.throttles.items()}
        self.pods = {pod_key(p): p for p in pods}
        self.requests = {k: pod_requests(p) for k, p in self.pods.items()}
        # (namespace, label key, value) -> pod keys: a term only looks at
        # the pods that carry one of the labels it requires
        by_label: Dict[Tuple[str, str, str], List[str]] = {}
        by_ns: Dict[str, List[str]] = {}
        for k, p in self.pods.items():
            ns = p["metadata"].get("namespace", "default")
            by_ns.setdefault(ns, []).append(k)
            for lk, lv in (p["metadata"].get("labels") or {}).items():
                by_label.setdefault((ns, lk, lv), []).append(k)
        # throttle key -> matched pod keys; pod key -> matched throttle keys
        self.matched: Dict[str, List[str]] = {}
        self.matching: Dict[str, List[str]] = {k: [] for k in self.pods}
        # terms and throttles that select alike are resolved once
        term_memo: Dict[str, List[str]] = {}
        for tkey, thr in self.throttles.items():
            found = set()
            for term in _terms(thr):
                if thr["kind"] == "Throttle":
                    nss = [thr["metadata"].get("namespace", "default")]
                else:
                    nss = [ns for ns, labels in self.ns_labels.items()
                           if selector_matches(term.get("namespaceSelector"), labels)]
                pod_sel = term.get("podSelector") or {}
                memo_key = json.dumps([nss, pod_sel], sort_keys=True)
                hits = term_memo.get(memo_key)
                if hits is None:
                    hits = term_memo[memo_key] = [
                        pk for ns in nss for pk in _candidates(pod_sel, ns, by_label, by_ns)
                        if selector_matches(pod_sel, self.pods[pk]["metadata"].get("labels") or {})]
                found.update(hits)
            self.matched[tkey] = sorted(found)
            for pk in found:
                self.matching[pk].append(tkey)
        usage_memo: Dict[Tuple[str, ...], tuple] = {}
        self.used = {}
        for tkey, pks in self.matched.items():
            sig = tuple(pks)
            if sig not in usage_memo:
                usage_memo[sig] = self.usage(tkey)
            self.used[tkey] = usage_memo[sig]

    def counted(self, pod: dict) -> bool:
        spec, status = pod.get("spec", {}), pod.get("status") or {}
        return (spec.get("schedulerName") == self.scheduler and bool(spec.get("nodeName"))
                and status.get("phase") not in ("Succeeded", "Failed"))

    def usage(self, tkey: str):
        used = ZERO
        for pk in self.matched[tkey]:
            if self.counted(self.pods[pk]):
                used = add(used, (1, self.requests[pk]))
        return used

    def bind(self, pk: str) -> None:
        """The pod is bound and Running: it counts in every matched used."""
        pod = self.pods[pk]
        pod["spec"]["nodeName"] = pod["spec"].get("nodeName") or "node-1"
        pod.setdefault("status", {})["phase"] = "Running"
        for tkey in self.matching[pk]:
            self.used[tkey] = add(self.used[tkey], (1, self.requests[pk]))

    def amounts(self, pks) -> Dict[str, tuple]:
        """{throttle key: summed amount} of pods ``pks`` over the throttles
        each matches."""
        out: Dict[str, tuple] = {}
        for pk in pks:
            for tkey in self.matching[pk]:
                out[tkey] = add(out.get(tkey, ZERO), (1, self.requests[pk]))
        return out

    def status_of(self, tkey: str, pk: str, extra=ZERO) -> str:
        """Throttle ``tkey``'s state for pod ``pk`` as PreFilter reads it
        (is_throttled_on_equal false; a Throttle's step 3 on equal), with
        ``extra`` counted on top of ``used`` as reserved."""
        cluster_kind = self.throttles[tkey]["kind"] == "ClusterThrottle"
        return check(self.thresholds[tkey], self.used[tkey], extra, self.requests[pk],
                     on_equal=False, step3_on_equal=not cluster_kind)

    def verdict(self, pk: str, extra: Optional[Dict[str, tuple]] = None) -> Dict[str, str]:
        """{throttle key: state} of every throttle that blocks pod ``pk``,
        with ``extra[throttle key]`` counted on top of ``used``."""
        out = {}
        for tkey in self.matching[pk]:
            st = self.status_of(tkey, pk, (extra or {}).get(tkey, ZERO))
            if st != NOT_THROTTLED:
                out[tkey] = st
        return out

    def schedulable(self, extra: Optional[Dict[str, tuple]] = None) -> Dict[str, bool]:
        """Every pod's verdict (``extra`` as for ``verdict``); pods that share
        matched throttles and requests share it, so it is computed once per
        such pair."""
        memo: Dict[Tuple, bool] = {}
        out = {}
        for pk in self.pods:
            sig = (tuple(self.matching[pk]), tuple(sorted(self.requests[pk].items())))
            ok = memo.get(sig)
            if ok is None:
                ok = memo[sig] = not self.verdict(pk, extra)
            out[pk] = ok
        return out

    def used_counts(self) -> Dict[str, int]:
        return {tkey: used[0] or 0 for tkey, used in self.used.items()}
