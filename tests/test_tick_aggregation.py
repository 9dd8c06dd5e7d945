"""The tick's ``used`` aggregation (``parallel/sharded.py``
``_used_by_target``: one sort of the [P·K] slots by target and prefix-sum
differences at each target's segment bounds) against the int64
scatter-add it replaced, kept here as a numpy reference: ``used_cnt``,
``used_req`` and ``contrib`` must agree bit for bit, wrap-around
included, and the fused tick program must return the same ``used``."""

import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from kube_throttler_tpu.ops.overrides import OverrideSchedule
from kube_throttler_tpu.ops.schema import PodBatch
from kube_throttler_tpu.parallel.sharded import _used_by_target, full_update_step_gather


def _scatter_reference(req, present, valid, counted, cols, T):
    """int64 scatter-add of every counted slot's row into its throttle;
    numpy's integer adds wrap like the device's."""
    slot = (cols >= 0) & (counted & valid)[:, None]
    pod, k = np.nonzero(slot)
    tgt = cols[pod, k]
    used_cnt = np.zeros(T, dtype=np.int64)
    used_req = np.zeros((T, req.shape[1]), dtype=np.int64)
    contrib = np.zeros((T, req.shape[1]), dtype=np.int32)
    np.add.at(used_cnt, tgt, 1)
    with np.errstate(over="ignore"):
        np.add.at(used_req, tgt, req[pod])
    np.add.at(contrib, tgt, present[pod].astype(np.int32))
    return used_cnt, used_req, contrib


def _case(name):
    """(req int64[P,R], present bool[P,R], valid bool[P], counted bool[P],
    cols int32[P,K], T) for one named case."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    P, K, T, R = 64, 4, 16, 3

    def base(P=P, K=K, T=T, R=R, lo=0, hi=10**6):
        req = rng.integers(lo, hi, size=(P, R), dtype=np.int64)
        present = rng.random((P, R)) < 0.7
        cols = rng.integers(-1, T, size=(P, K)).astype(np.int32)
        return req, present, np.ones(P, bool), np.ones(P, bool), cols, T

    if name == "random":
        req, present, valid, counted, cols, T = base()
        valid[::7] = False
        counted[::5] = False
    elif name == "near_2_62_wrap":
        # every pod's rows near ±2^62 into 2 throttles: prefix sums and the
        # per-throttle sums themselves wrap past int64
        req, present, valid, counted, cols, T = base(lo=2**62 - 2**20, hi=2**62)
        req[1::2] *= -1
        req[::3] += 2**62 - 1
        cols = rng.integers(0, 2, size=(P, K)).astype(np.int32)
    elif name == "every_pod_one_throttle":
        req, present, valid, counted, cols, T = base()
        cols[:, 0] = 5
        cols[:, 1:] = -1
    elif name == "all_padded":
        req, present, valid, counted, cols, T = base()
        cols[:] = -1
    elif name == "uncounted_and_invalid":
        # 50 × 3 slots: the last row of 128 is partly padding
        req, present, valid, counted, cols, T = base(P=50, K=3)
        valid[:25] = False
        counted[10:40] = False
        cols = rng.integers(0, T, size=cols.shape).astype(np.int32)
    elif name == "targets_0_and_last":
        req, present, valid, counted, cols, T = base()
        cols = np.where(rng.random((P, K)) < 0.5, 0, T - 1).astype(np.int32)
    elif name == "single_throttle":
        # 37 × 3 slots: fewer than one row of 128
        req, present, valid, counted, cols, T = base(P=37, K=3, T=1)
        cols = rng.integers(-1, 1, size=cols.shape).astype(np.int32)
    elif name == "duplicate_cols":
        req, present, valid, counted, cols, T = base()
        cols[:, 1] = cols[:, 0]
    elif name == "zero_and_absent_dims":
        req, present, valid, counted, cols, T = base()
        req[::2] = 0
        present[1::3] = False
    elif name == "every_slot_counted":
        # no slot drops: the last bound is the slot count itself
        req, present, valid, counted, cols, T = base()
        cols = rng.integers(0, T, size=(P, K)).astype(np.int32)
    else:
        raise AssertionError(name)
    return req, present, valid, counted, cols, T


CASES = [
    "random", "near_2_62_wrap", "every_pod_one_throttle", "all_padded",
    "uncounted_and_invalid", "targets_0_and_last", "single_throttle",
    "duplicate_cols", "zero_and_absent_dims", "every_slot_counted",
]


@pytest.mark.parametrize("name", CASES)
def test_used_matches_scatter_add(name):
    req, present, valid, counted, cols, T = _case(name)
    want = _scatter_reference(req, present, valid, counted, cols, T)
    if name == "near_2_62_wrap":  # the case is only worth its name if sums wrap
        exact = np.zeros((T, req.shape[1]), dtype=object)
        for p, k in zip(*np.nonzero(cols >= 0)):
            exact[cols[p, k]] += req[p].astype(object)
        assert (exact != want[1].astype(object)).any()
    pods = PodBatch(valid=jnp.asarray(valid), req=jnp.asarray(req),
                    req_present=jnp.asarray(present))
    slot = (cols >= 0) & (counted & valid)[:, None]
    tgt = jnp.asarray(np.where(slot, cols, T).reshape(-1).astype(np.int32))
    got = [np.asarray(a) for a in _used_by_target(pods, tgt, T)]
    for g, w, what in zip(got, want, ("used_cnt", "used_req", "contrib")):
        assert g.dtype == w.dtype, what
        np.testing.assert_array_equal(g, w, err_msg=what)

    # the fused tick returns the same used, from the same cols
    R = req.shape[1]
    z = lambda *s: jnp.zeros(s, dtype=jnp.int64)  # noqa: E731
    f = lambda *s: jnp.zeros(s, dtype=bool)  # noqa: E731
    sched = OverrideSchedule(
        ov_valid=f(T, 1), ov_begin=z(T, 1), ov_end=z(T, 1), ov_cnt=z(T, 1),
        ov_cnt_present=f(T, 1), ov_req=z(T, 1, R), ov_req_present=f(T, 1, R),
        spec_cnt=z(T), spec_cnt_present=f(T), spec_req=z(T, R), spec_req_present=f(T, R),
    )
    out = full_update_step_gather(
        sched, pods, jnp.asarray(cols), jnp.asarray(counted), z(T), f(T), z(T, R),
        f(T, R), jnp.ones(T, dtype=bool), jnp.int64(0),
    )
    np.testing.assert_array_equal(np.asarray(out[2]), want[0])
    np.testing.assert_array_equal(np.asarray(out[3]), want[1])
