#!/usr/bin/env python
"""Remote-mode burst smoke against a REAL kube-apiserver (kind).

The real-cluster twin of the reference's headline integration case
(test/integration/throttle_test.go:167-197): a Throttle capping cpu=1,
21 pods of 100m each pre_filter'd with reservations — exactly 10 must
admit... (cpu=1 / 100m = 10; the reference uses 50m for 20). Here:
cpu=1 vs 21 x 50m pods -> exactly 20 admitted.

Unlike the in-repo mockserver tier, this drives the daemon's remote mode
through a genuine apiserver: CRD schema validation/defaulting, real
resourceVersion semantics, real watch cadence. Run after hack/dev/up.sh:

    python hack/dev/burst_smoke.py [--kubeconfig .dev/kubeconfig]

Exit 0 = 20 admitted, statuses converged on the cluster; nonzero + log
otherwise. (See docs/mockserver-fidelity.md for what this covers that the
mock cannot.)
"""

import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)

from kube_throttler_tpu.api import ResourceAmount, Throttle, ThrottleSpec  # noqa: E402
from kube_throttler_tpu.api.pod import make_pod  # noqa: E402
from kube_throttler_tpu.api.serialization import object_to_dict  # noqa: E402
from kube_throttler_tpu.api.types import (  # noqa: E402
    LabelSelector,
    ThrottleSelector,
    ThrottleSelectorTerm,
)
from kube_throttler_tpu.client.transport import (  # noqa: E402
    GROUP,
    VERSION,
    RemoteSession,
    parse_kubeconfig,
)
from kube_throttler_tpu.engine.store import Store  # noqa: E402
from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--kubeconfig", default=os.path.join(REPO_ROOT, ".dev", "kubeconfig")
    )
    ap.add_argument("--namespace", default="default")
    args = ap.parse_args()

    config = parse_kubeconfig(args.kubeconfig)
    store = Store()
    session = RemoteSession(config, store)
    client = session.client
    ns = args.namespace

    thr = Throttle(
        name="smoke-burst",
        namespace=ns,
        spec=ThrottleSpec(
            throttler_name="kube-throttler",
            threshold=ResourceAmount.of(requests={"cpu": "1"}),
            selector=ThrottleSelector(
                selector_terms=(
                    ThrottleSelectorTerm(
                        LabelSelector(match_labels={"smoke": "burst"})
                    ),
                )
            ),
        ),
    )
    base = f"/apis/{GROUP}/{VERSION}/namespaces/{ns}/throttles"
    doc = object_to_dict(thr)
    try:
        client.post(base, doc)
        print(f"created Throttle {ns}/smoke-burst on the cluster")
    except Exception as e:  # already exists from a previous run
        print(f"throttle create: {e} (continuing)")

    session.start(sync_timeout=60)
    plugin = KubeThrottler(
        decode_plugin_args(
            {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
        ),
        store,
        use_device=True,
        start_workers=True,
        status_writer=session.status_committer,
    )
    try:
        # wait for the throttle to appear through the real watch
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                store.get_throttle(ns, "smoke-burst")
                break
            except Exception:
                time.sleep(0.25)
        else:
            print("FAIL: throttle never arrived through the watch")
            return 1

        admitted = 0
        for i in range(21):
            pod = make_pod(
                f"smoke-b{i}",
                namespace=ns,
                labels={"smoke": "burst"},
                requests={"cpu": "50m"},
            )
            status = plugin.pre_filter(pod)
            if status.is_success():
                plugin.reserve(pod)
                admitted += 1
        print(f"burst: {admitted}/21 admitted (want exactly 20)")
        if admitted != 20:
            return 1

        # the reconcile's status PUT must land on the REAL status
        # subresource and round-trip through the watch
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            got = client.get(f"{base}/smoke-burst")
            status = got.get("status") or {}
            if status.get("throttled") is not None:
                print(f"status on cluster: {status.get('throttled')}")
                return 0
            time.sleep(0.5)
        print("FAIL: status never materialized on the cluster")
        return 1
    finally:
        plugin.stop()
        session.stop()


if __name__ == "__main__":
    sys.exit(main())
