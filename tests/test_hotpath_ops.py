"""Property tests for two hot paths:

- check_pod's host single-pod tiers must agree with the dense check
  restricted to the pod's matched rows, for every (on_equal,
  step3_on_equal) variant, invalid rows and repeated rows included;
- ``apply_pod_deltas_batched`` must equal sequential ``apply_pod_delta``
  application (scatter-adds commute exactly in int64).
"""

import random

import numpy as np
import pytest

from kube_throttler_tpu.ops.aggregate import apply_pod_delta, apply_pod_deltas_batched
from kube_throttler_tpu.ops.check import check_pods
from kube_throttler_tpu.ops.schema import PodBatch, ThrottleState

from tests.test_check_kernel import _host_tier_statuses


def _rand_state(npr, T, R):
    """Random padded ThrottleState with adversarial presence masks."""
    thr_req = npr.integers(0, 2000, (T, R)).astype(np.int64)
    used_req = npr.integers(0, 2000, (T, R)).astype(np.int64)
    res_req = npr.integers(0, 500, (T, R)).astype(np.int64)
    return ThrottleState(
        valid=npr.random(T) < 0.9,
        thr_cnt=npr.integers(0, 10, T).astype(np.int64),
        thr_cnt_present=npr.random(T) < 0.8,
        thr_req=thr_req,
        thr_req_present=npr.random((T, R)) < 0.8,
        used_cnt=npr.integers(0, 12, T).astype(np.int64),
        used_cnt_present=npr.random(T) < 0.8,
        used_req=used_req,
        used_req_present=npr.random((T, R)) < 0.8,
        res_cnt=npr.integers(0, 3, T).astype(np.int64),
        res_cnt_present=npr.random(T) < 0.4,
        res_req=res_req,
        res_req_present=npr.random((T, R)) < 0.4,
        st_cnt_throttled=npr.random(T) < 0.3,
        st_req_throttled=npr.random((T, R)) < 0.3,
        st_req_flag_present=npr.random((T, R)) < 0.6,
    )


def _rand_pod(rng, R):
    req = np.zeros(R, dtype=np.int64)
    present = np.zeros(R, dtype=bool)
    for r in range(R):
        if rng.random() < 0.7:
            req[r] = rng.randrange(0, 2000)
            present[r] = True
    return req, present


@pytest.mark.parametrize("on_equal,s3", [(False, True), (True, True), (False, False)])
def test_indexed_matches_dense(on_equal, s3):
    rng = random.Random(42)
    npr = np.random.default_rng(42)
    for trial in range(20):
        T, R, K = 37, 5, 8
        state = _rand_state(npr, T, R)
        pod_req, pod_present = _rand_pod(rng, R)
        # up to K matched rows, repeats allowed
        cols = npr.integers(0, T, rng.randrange(0, K + 1))

        batch = PodBatch(
            valid=np.ones(1, dtype=bool), req=pod_req[None], req_present=pod_present[None]
        )
        dense = np.asarray(
            check_pods(state, batch, np.ones((1, T), dtype=bool), on_equal, s3)
        )[0]
        tiers = _host_tier_statuses(state, cols, pod_req, pod_present, on_equal, s3)
        for tier, got in tiers.items():
            np.testing.assert_array_equal(got, dense[cols], err_msg=f"{tier} trial {trial}")


def test_batched_deltas_match_sequential():
    npr = np.random.default_rng(7)
    T, R, N, K = 23, 4, 50, 3
    used_cnt = npr.integers(0, 100, T).astype(np.int64)
    used_req = npr.integers(0, 10_000, (T, R)).astype(np.int64)
    contrib = npr.integers(0, 20, (T, R)).astype(np.int32)

    # pad ~20% of slots out-of-range (row T) — scatter must drop them
    ids = npr.integers(0, T + 1, (N, K)).astype(np.int32)
    signs = npr.choice(np.array([-1, 0, 1], dtype=np.int64), (N, K))
    pod_req = npr.integers(0, 500, (N, R)).astype(np.int64)
    pod_present = npr.random((N, R)) < 0.8

    seq = (used_cnt.copy(), used_req.copy(), contrib.copy())
    for i in range(N):
        seq = apply_pod_delta(*seq, ids[i], signs[i], pod_req[i], pod_present[i])
    seq = [np.asarray(a) for a in seq]

    bat = apply_pod_deltas_batched(
        used_cnt, used_req, contrib, ids, signs, pod_req, pod_present
    )
    for got, want in zip(bat, seq):
        np.testing.assert_array_equal(np.asarray(got), want)
