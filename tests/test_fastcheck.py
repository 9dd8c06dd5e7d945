"""check_pod's single-pod fast path, the host tiers, must be bit-identical
to the direct dense kernel on the pod's matched rows."""

import random

import numpy as np
import pytest

from kube_throttler_tpu.ops import DimRegistry, check_pods, encode_pods, encode_throttle_state

from tests.test_check_kernel import _build_objects, _host_tier_statuses


@pytest.mark.parametrize("kind", ["throttle", "clusterthrottle"])
@pytest.mark.parametrize("on_equal", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fast_matches_direct(kind, on_equal, seed):
    rng = random.Random(seed)
    throttles, reserved, pods = _build_objects(rng, n_throttles=40, n_pods=30, kind=kind)
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, reserved=reserved)
    batch = encode_pods(pods, dims)
    mask = np.asarray(rng.choices([True, False], k=len(pods) * len(throttles))).reshape(
        len(pods), len(throttles)
    )
    step3 = True if kind == "throttle" else on_equal

    direct = np.asarray(check_pods(state, batch, mask, on_equal=on_equal, step3_on_equal=step3))
    req, present = np.asarray(batch.req), np.asarray(batch.req_present)
    for i in range(len(pods)):
        cols = np.nonzero(mask[i])[0]
        tiers = _host_tier_statuses(state, cols, req[i], present[i], on_equal, step3)
        for tier, got in tiers.items():
            np.testing.assert_array_equal(got, direct[i, cols], err_msg=f"{tier} pod {i}")
