"""Property tests: the batched XLA check kernel must agree cell-for-cell
with the pure-Python oracle (api.types.check_throttled_for) across presence
and equality-boundary edge cases."""

import random
from datetime import datetime, timezone

import jax
import numpy as np
import pytest

from kube_throttler_tpu.api import (
    ClusterThrottle,
    ClusterThrottleSpec,
    IsResourceAmountThrottled,
    ResourceAmount,
    Throttle,
    ThrottleSpec,
)
from kube_throttler_tpu.api.pod import make_pod
from kube_throttler_tpu.api.types import CalculatedThreshold, ThrottleStatus
from kube_throttler_tpu.engine import devicestate as ds
from kube_throttler_tpu.ops import (
    CHECK_NOT_AFFECTED,
    STATUS_NAMES,
    DimRegistry,
    check_pods,
    check_pods_compact,
    encode_pods,
    encode_throttle_state,
)

NOW = datetime(2024, 1, 15, tzinfo=timezone.utc)
RESOURCES = ["cpu", "memory", "nvidia.com/gpu"]
# values chosen to sit on comparison boundaries (milli-units as strings)
BOUNDARY_VALUES = ["0", "100m", "200m", "300m", "1"]


def _random_amount(rng, allow_nil_counts=True) -> ResourceAmount:
    counts = None
    if not allow_nil_counts or rng.random() < 0.7:
        counts = rng.choice([0, 1, 2, 3, 5])
    requests = None
    if rng.random() < 0.85:
        requests = {}
        for r in RESOURCES:
            if rng.random() < 0.6:
                requests[r] = rng.choice(BOUNDARY_VALUES)
    return ResourceAmount.of(pod=counts, requests=requests)


def _random_flags(rng) -> IsResourceAmountThrottled:
    req = None
    if rng.random() < 0.7:
        req = {r: rng.random() < 0.3 for r in RESOURCES if rng.random() < 0.6}
    return IsResourceAmountThrottled(
        resource_counts_pod=rng.random() < 0.2, resource_requests=req
    )


def _random_status(rng) -> ThrottleStatus:
    calc = CalculatedThreshold()
    if rng.random() < 0.5:
        calc = CalculatedThreshold(threshold=_random_amount(rng), calculated_at=NOW)
    return ThrottleStatus(
        calculated_threshold=calc,
        throttled=_random_flags(rng),
        used=_random_amount(rng),
    )


def _build_objects(rng, n_throttles, n_pods, kind):
    throttles = []
    reserved = []
    for i in range(n_throttles):
        if kind == "throttle":
            throttles.append(
                Throttle(
                    name=f"t{i}",
                    spec=ThrottleSpec(threshold=_random_amount(rng)),
                    status=_random_status(rng),
                )
            )
        else:
            throttles.append(
                ClusterThrottle(
                    name=f"c{i}",
                    spec=ClusterThrottleSpec(threshold=_random_amount(rng)),
                    status=_random_status(rng),
                )
            )
        reserved.append(
            _random_amount(rng) if rng.random() < 0.6 else ResourceAmount()
        )
    pods = []
    for i in range(n_pods):
        reqs = {}
        for r in RESOURCES:
            if rng.random() < 0.6:
                reqs[r] = rng.choice(BOUNDARY_VALUES)
        pods.append(make_pod(f"p{i}", requests=reqs))
    return throttles, reserved, pods


class _StagingPlanes:
    """A ThrottleState's planes under the staging names that check_pod's
    host tiers read from a kind's state."""

    def __init__(self, state):
        for field, attr in ds._KindState._THR_FIELDS:
            setattr(self, attr, np.ascontiguousarray(np.asarray(getattr(state, field))))
        self.R = self.thr_req.shape[1]
        self._cls_cache = self._cls_scratch = None


def _host_tier_statuses(state, cols, pod_req, pod_present, on_equal, step3):
    """{tier: int8[K]} statuses of one pod against the state rows ``cols``
    from each host tier of check_pod: numpy always, native C++ where the
    library loads."""
    planes = _StagingPlanes(state)
    pod_req = np.ascontiguousarray(pod_req, dtype=np.int64)
    pod_present = np.ascontiguousarray(pod_present, dtype=bool)
    rows = ds.DeviceStateManager._gather_check_rows(planes, cols)
    out = {"numpy": ds._host_classify_rows(rows, pod_req, pod_present, on_equal, step3)}
    lib = ds._native_cls_lib()
    if lib is not None:
        out["native"] = ds._native_classify_cols(
            lib, planes, cols, pod_req, pod_present, on_equal, step3
        )
    return out


@pytest.mark.parametrize("kind", ["throttle", "clusterthrottle"])
@pytest.mark.parametrize("on_equal", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_matches_oracle(kind, on_equal, seed):
    rng = random.Random(seed)
    throttles, reserved, pods = _build_objects(rng, n_throttles=40, n_pods=30, kind=kind)

    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, reserved=reserved)
    batch = encode_pods(pods, dims)
    mask = np.asarray(rng.choices([True, False], k=len(pods) * len(throttles))).reshape(
        len(pods), len(throttles)
    )

    step3 = True if kind == "throttle" else on_equal
    got = np.asarray(check_pods(state, batch, mask, on_equal=on_equal, step3_on_equal=step3))

    for i, pod in enumerate(pods):
        for j, thr in enumerate(throttles):
            if not mask[i, j]:
                assert got[i, j] == CHECK_NOT_AFFECTED
                continue
            want = thr.check_throttled_for(pod, reserved[j], on_equal)
            assert STATUS_NAMES[int(got[i, j])] == want, (
                f"seed={seed} kind={kind} on_equal={on_equal} pod={i} thr={j}: "
                f"kernel={STATUS_NAMES[int(got[i, j])]} oracle={want} "
                f"thr={thr} pod_req={pod.spec.containers[0].requests} reserved={reserved[j]}"
            )


def test_compact_counts_match_full():
    rng = random.Random(7)
    throttles, reserved, pods = _build_objects(rng, 25, 20, "throttle")
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, reserved=reserved)
    batch = encode_pods(pods, dims)
    mask = np.ones((20, 25), dtype=bool)

    full = np.asarray(check_pods(state, batch, mask))
    counts, schedulable = check_pods_compact(state, batch, mask)
    counts = np.asarray(counts)
    schedulable = np.asarray(schedulable)
    for i in range(20):
        for c in range(4):
            assert counts[i, c] == np.sum(full[i] == c)
        assert schedulable[i] == (np.sum((full[i] > 0)) == 0)


def test_padding_rows_are_not_affected():
    throttles = [Throttle(name="t0", spec=ThrottleSpec(threshold=ResourceAmount.of(pod=1)))]
    pods = [make_pod("p0", requests={"cpu": "1"})]
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, capacity=8)
    batch = encode_pods(pods, dims, capacity=4)
    mask = np.ones((4, 8), dtype=bool)
    got = np.asarray(check_pods(state, batch, mask))
    assert got.shape == (4, 8)
    assert (got[1:, :] == CHECK_NOT_AFFECTED).all()
    assert (got[:, 1:] == CHECK_NOT_AFFECTED).all()
    assert got[0, 0] != CHECK_NOT_AFFECTED


def _cols_of_mask(mask: np.ndarray, K: int) -> np.ndarray:
    """[P,T] bool → int32[P,K] matched cols, -1 padded (test-local twin of
    _KindState._cols_from_mask)."""
    P = mask.shape[0]
    out = np.full((P, K), -1, dtype=np.int32)
    for i in range(P):
        cols = np.nonzero(mask[i])[0]
        out[i, : cols.size] = cols
    return out


_BIG = 2**62
# int64 values on the limb boundaries of the sparse kernel's (hi, lo)
# int32 split and near 2^62, the largest the overflow-free domain allows
_EDGE = [0, 1, 2, 99, 100, 101, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**32 + 1,
         _BIG - 1, _BIG, _BIG + 1]
_AU_MAX = _BIG - 2  # used + reserved; with a pod ≤ 2^62 + 1 no sum overflows


def _edge_state(rng, T, P, R):
    """Raw (ThrottleState, PodBatch) planes of width R: absent and zero
    dims on both sides, pods that request zero, used + reserved one under,
    at and over thresholds near 2^62 and on limb boundaries, invalid
    throttles and pods (with data in them), status flags."""
    from kube_throttler_tpu.ops import PodBatch, ThrottleState

    def present(shape, p):
        return np.asarray([rng.random() < p for _ in range(int(np.prod(shape)))]).reshape(shape)

    def au_near(thr):
        au = thr + rng.choice([-1, 0, 1]) if rng.random() < 0.7 else rng.choice(_EDGE)
        return min(max(au, 0), _AU_MAX)

    thr_req_present, au_req_present = present((T, R), 0.6), present((T, R), 0.7)
    thr_req = np.zeros((T, R), dtype=np.int64)
    used_req = np.zeros((T, R), dtype=np.int64)
    res_req = np.zeros((T, R), dtype=np.int64)
    for t in range(T):
        for r in range(R):
            thr = rng.choice(_EDGE)
            au = au_near(thr)
            thr_req[t, r] = thr if thr_req_present[t, r] else 0
            if au_req_present[t, r]:
                used_req[t, r] = au // 2
                res_req[t, r] = au - au // 2
    thr_cnt_present, au_cnt_present = present((T,), 0.6), present((T,), 0.7)
    thr_cnt = np.where(thr_cnt_present, [rng.choice([0, 1, 2, 3, 5]) for _ in range(T)], 0)
    used_cnt = np.where(au_cnt_present, [rng.choice([0, 1, 2, 3]) for _ in range(T)], 0)
    res_cnt = np.where(au_cnt_present, [rng.choice([0, 1]) for _ in range(T)], 0)
    st_flag_present = present((T, R), 0.5)
    state = ThrottleState(
        valid=present((T,), 0.85),
        thr_cnt=thr_cnt.astype(np.int64), thr_cnt_present=thr_cnt_present,
        thr_req=thr_req, thr_req_present=thr_req_present,
        used_cnt=used_cnt.astype(np.int64), used_cnt_present=au_cnt_present & present((T,), 0.8),
        used_req=used_req, used_req_present=au_req_present & present((T, R), 0.8),
        res_cnt=res_cnt.astype(np.int64), res_cnt_present=au_cnt_present & present((T,), 0.5),
        res_req=res_req, res_req_present=au_req_present & present((T, R), 0.5),
        st_cnt_throttled=present((T,), 0.2),
        st_req_throttled=st_flag_present & present((T, R), 0.4),
        st_req_flag_present=st_flag_present,
    )
    resid = thr_req - (used_req + res_req)
    # a few dims a pod, most on the bitmask's word edges, so that one dim
    # alone often decides a slot
    hot = sorted({d for d in (0, 1, 31, 32, R - 1) if d < R})
    req_present = np.zeros((P, R), dtype=bool)
    req = np.zeros((P, R), dtype=np.int64)
    for p in range(P):
        dims = rng.sample(hot, k=rng.randint(0, 3)) + [rng.randrange(R)] * (rng.random() < 0.3)
        for r in dims:
            req_present[p, r] = True
            if rng.random() < 0.5:
                # one under, at or over some throttle's step-4 residual
                v = int(resid[rng.randrange(T), r]) + rng.choice([-1, 0, 1])
            else:
                v = rng.choice(_EDGE)
            req[p, r] = min(max(v, 0), _BIG + 1)
    pods = PodBatch(valid=present((P,), 0.85), req=req, req_present=req_present)
    return state, pods


def _edge_cols(rng, P, T, K):
    """int32[P,K] cols: -1 pads anywhere in a row, cols of invalid
    throttles, duplicates, one all-pad row."""
    cols = np.asarray(
        [[-1 if rng.random() < 0.25 else rng.randrange(T) for _ in range(K)] for _ in range(P)],
        dtype=np.int32,
    )
    cols[0, :] = -1
    cols[1, 1:] = cols[1, 0] = max(int(cols[1, 0]), 0)
    return cols


def _assert_gather_is_dense(state, batch, cols, on_equal, step3):
    """The sparse statuses are the dense [P,T] check_pods read through
    the cols (padded slots NOT_AFFECTED), and the compact outputs follow."""
    from kube_throttler_tpu.ops.check import (
        _gather_statuses_blocked,
        check_pods_gather,
        statuses_to_compact,
    )

    P, T = batch.req.shape[0], state.thr_req.shape[0]
    dense = np.asarray(check_pods(state, batch, np.ones((P, T), dtype=bool),
                                  on_equal=on_equal, step3_on_equal=step3))
    want = np.where(cols >= 0, np.take_along_axis(dense, np.maximum(cols, 0), axis=1),
                    CHECK_NOT_AFFECTED)
    statuses = jax.jit(_gather_statuses_blocked, static_argnums=(3, 4))
    got = np.asarray(statuses(state, batch, cols, on_equal, step3))
    np.testing.assert_array_equal(got, want)
    want_counts, want_ok = statuses_to_compact(want)
    got_counts, got_ok = check_pods_gather(
        state, batch, cols, on_equal=on_equal, step3_on_equal=step3)
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))


# (kind, on_equal, step3_on_equal, seed, R). R None: throttle objects
# through the encoders, step 3's flag from the kind. R set: raw planes of
# that width (40 needs two bitmask words) with int64 edge values, every
# (on_equal, step3_on_equal) pair.
_GATHER_CASES = [
    pytest.param(kind, on_equal, None, seed, None, id=f"{seed}-{on_equal}-{kind}")
    for seed in (0, 3) for on_equal in (False, True)
    for kind in ("throttle", "clusterthrottle")
] + [
    pytest.param("raw", on_equal, step3, seed, R,
                 id=f"R{R}-{seed}-{on_equal}-{step3}")
    for R in (8, 16, 40) for seed in (1, 2)
    for on_equal in (False, True) for step3 in (False, True)
]


@pytest.mark.parametrize("kind,on_equal,step3,seed,R", _GATHER_CASES)
def test_gather_matches_compact(kind, on_equal, step3, seed, R):
    """check_pods_gather over [P,K] matched cols must equal
    check_pods_compact over the equivalent [P,T] mask — counts AND gate —
    and its per-slot statuses the dense check_pods read through the cols."""
    from kube_throttler_tpu.ops import check_pods_gather

    rng = random.Random(seed)
    if R is not None:
        state, batch = _edge_state(rng, T=24, P=20, R=R)
        _assert_gather_is_dense(state, batch, _edge_cols(rng, 20, 24, 12), on_equal, step3)
        return
    throttles, reserved, pods = _build_objects(rng, n_throttles=17, n_pods=23, kind=kind)
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, reserved=reserved)
    batch = encode_pods(pods, dims)
    # sparse-ish random mask incl. empty rows and one full row
    mask = np.asarray(
        rng.choices([True, False], weights=[1, 4], k=len(pods) * len(throttles))
    ).reshape(len(pods), len(throttles))
    mask[0, :] = False
    mask[1, :] = True
    cols = _cols_of_mask(mask, K=int(mask.sum(axis=1).max()))

    step3 = True if kind == "throttle" else on_equal
    want_counts, want_ok = check_pods_compact(
        state, batch, mask, on_equal=on_equal, step3_on_equal=step3
    )
    got_counts, got_ok = check_pods_gather(
        state, batch, cols, on_equal=on_equal, step3_on_equal=step3
    )
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    _assert_gather_is_dense(state, batch, cols, on_equal, step3)


def test_gather_lowers_to_one_row_gather():
    """The sparse kernel reads each slot's throttle with ONE gather of a
    whole packed record ([1, W] slices, W = 2 + 4R at R ≤ 32), at both
    widths and in both the single and the blocked form; the element
    gathers of the raw state planes it replaced (24 in the lowered
    module) must not come back unnoticed."""
    import re

    from kube_throttler_tpu.ops import check_pods_gather

    for R, P, K in ((8, 8, 4), (16, 8, 4), (8, 4096, 256)):  # the last one blocks
        dims = DimRegistry(capacity=R)
        state = encode_throttle_state([], dims, capacity=16)
        batch = encode_pods([], dims, capacity=P)
        text = check_pods_gather.lower(state, batch, np.full((P, K), -1, np.int32)).as_text()
        slices = re.findall(r'"stablehlo\.gather".*?slice_sizes = array<i64: ([\d, ]+)>', text)
        assert slices == [f"1, {2 + 4 * R}"], (R, P, K, slices)


@pytest.mark.parametrize("seed", [0, 7])
def test_gather_blocked_matches_unblocked(seed, monkeypatch):
    """The P-chunked gather decomposition (lax.map blocks, activated when
    the gathered records' P×K×128 padded elements exceed
    KT_GATHER_CHUNK_ELEMS — the r5 full-scale TPU OOM fix) must be bit-identical to the single-dispatch path, including when
    P does not divide evenly into blocks."""
    from kube_throttler_tpu.ops import check, check_pods_gather

    rng = random.Random(seed)
    throttles, reserved, pods = _build_objects(rng, n_throttles=9, n_pods=29, kind="throttle")
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, reserved=reserved)
    batch = encode_pods(pods, dims)
    mask = np.asarray(
        rng.choices([True, False], weights=[1, 3], k=len(pods) * len(throttles))
    ).reshape(len(pods), len(throttles))
    cols = _cols_of_mask(mask, K=max(1, int(mask.sum(axis=1).max())))

    # un-jitted bodies: the jitted wrappers cache by shape, so the chunk
    # threshold (read at trace time) must be exercised through the raw
    # functions for the monkeypatch to take effect
    want = np.asarray(check._gather_statuses(state, batch, cols, False, True))
    # force 4-pod blocks of 128-lane records (29 pods ⇒ a ragged final
    # block exercises padding)
    monkeypatch.setattr(check, "_GATHER_CHUNK_ELEMS", 4 * cols.shape[1] * 128)
    got = np.asarray(check._gather_statuses_blocked(state, batch, cols, False, True))
    np.testing.assert_array_equal(got, want)
    # and through the compact reduction (counts + schedulable gate)
    want_c, want_ok = check_pods_gather(state, batch, cols)
    got_c = check.statuses_to_compact(got)
    np.testing.assert_array_equal(np.asarray(got_c[0]), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_c[1]), np.asarray(want_ok))


def test_gather_ignores_padding_and_invalid_cols():
    """-1 pad slots and cols pointing at invalid (freed) throttle slots must
    contribute nothing."""
    from kube_throttler_tpu.ops import check_pods_gather

    throttles = [Throttle(name="t0", spec=ThrottleSpec(threshold=ResourceAmount.of(pod=1)))]
    pods = [make_pod("p0", requests={"cpu": "1"})]
    dims = DimRegistry()
    state = encode_throttle_state(throttles, dims, capacity=8)
    batch = encode_pods(pods, dims, capacity=4)
    # slot 0 → the real throttle; slot 1 → padding col 5 (invalid); rest -1
    cols = np.full((4, 4), -1, dtype=np.int32)
    cols[0, 0] = 0
    cols[0, 1] = 5
    counts, ok = check_pods_gather(state, batch, cols)
    counts = np.asarray(counts)
    assert counts[0].sum() == 1  # only the valid throttle counted
    assert counts[1:].sum() == 0  # invalid pod rows contribute nothing
    assert not bool(np.asarray(ok)[0]) or counts[0, 0] == 1


def test_native_handle_lifecycle():
    """The C-side classifier handle must be destroyed exactly once: early
    at plane re-registration (capacity growth replaces staging arrays) OR
    at _KindState GC — weakref.finalize guarantees at-most-once, so the
    two paths cannot double-free."""
    import gc

    from kube_throttler_tpu.ops.schema import DimRegistry

    lib = ds._native_cls_lib()
    if lib is None:
        pytest.skip("native lib unavailable (KT_TPU_NO_NATIVE or no toolchain)")
    ks = ds._KindState("throttle", DimRegistry())
    cols = np.array([0, 1], dtype=np.int64)
    pod_req = np.zeros(ks.R, dtype=np.int64)
    pod_present = np.zeros(ks.R, dtype=bool)
    ds._native_classify_cols(lib, ks, cols, pod_req, pod_present, False, True)
    fin = ks._cls_cache[3]
    assert fin.alive
    ks.thr_cnt = ks.thr_cnt.copy()  # a growth-like plane replacement
    ds._native_classify_cols(lib, ks, cols, pod_req, pod_present, False, True)
    assert not fin.alive, "re-registration must retire the old handle"
    fin2 = ks._cls_cache[3]
    assert fin2.alive and fin2 is not fin
    del ks
    gc.collect()
    assert not fin2.alive, "GC must retire the live handle"


def _live_stack():
    """A served stack with randomized live state (40 throttles over 5
    label groups, 120 bound pods), and 24 probe pods not in the store.
    Returns (plugin, probes)."""
    import random
    from dataclasses import replace

    from kube_throttler_tpu.api import ResourceAmount, Throttle, ThrottleSpec
    from kube_throttler_tpu.api.pod import Namespace, make_pod
    from kube_throttler_tpu.api.types import (
        LabelSelector,
        ThrottleSelector,
        ThrottleSelectorTerm,
    )
    from kube_throttler_tpu.engine.store import Store
    from kube_throttler_tpu.plugin import KubeThrottler, decode_plugin_args

    rng = random.Random(23)
    store = Store()
    store.create_namespace(Namespace("default"))
    plugin = KubeThrottler(
        decode_plugin_args(
            {"name": "kube-throttler", "targetSchedulerName": "my-scheduler"}
        ),
        store,
        use_device=True,
    )
    for i in range(40):
        store.create_throttle(
            Throttle(
                name=f"t{i}",
                namespace="default",
                spec=ThrottleSpec(
                    throttler_name="kube-throttler",
                    threshold=ResourceAmount.of(
                        pod=rng.choice([None, 1, 2, 5]),
                        requests={
                            "cpu": f"{rng.randrange(1, 9) * 100}m",
                            "memory": f"{rng.randrange(1, 5)}Gi",
                        },
                    ),
                    selector=ThrottleSelector(
                        selector_terms=(
                            ThrottleSelectorTerm(
                                LabelSelector(match_labels={"grp": f"g{i % 5}"})
                            ),
                        )
                    ),
                ),
            )
        )
    for i in range(120):
        p = make_pod(
            f"p{i}",
            namespace="default",
            labels={"grp": f"g{rng.randrange(5)}"},
            requests={
                "cpu": f"{rng.randrange(1, 6) * 100}m",
                "memory": f"{rng.randrange(1, 3)}Gi",
            },
        )
        p = replace(p, spec=replace(p.spec, node_name="n1"))
        p.status.phase = "Running"
        store.create_pod(p)
    plugin.run_pending_once()
    probes = [
        make_pod(
            f"q{i}",
            namespace="default",
            labels={"grp": f"g{i % 5}"},
            requests={"cpu": f"{rng.randrange(1, 9) * 100}m"},
        )
        for i in range(24)
    ]
    return plugin, probes


def test_host_single_check_matches_device_kernel():
    """check_pod's HOST route (indexed_check_max affected throttles or
    fewer) must agree cell-for-cell with its dense device fallback
    (``check_pods`` over the pod's mask row, forced by indexed_check_max
    = 0) on randomized live state — the two are line-for-line ports of
    the same 4-step resolution and this pins them together."""
    plugin, probes = _live_stack()
    dm = plugin.device_manager
    # the test pins BOTH routes against each other explicitly by setting
    # the route threshold per iteration
    for on_equal in (False, True):
        for kind in ("throttle", "clusterthrottle"):
            for p in probes:
                dm.indexed_check_max = 1024
                host = dm.check_pod(p, kind, on_equal)
                dm.indexed_check_max = 0
                dev = dm.check_pod(p, kind, on_equal)
                assert host == dev, (kind, on_equal, p.name, host, dev)
    dm.indexed_check_max = 1024

    # the host route has two tiers (native C++ ktn_cls_run when the lib
    # loads, numpy _host_classify_rows otherwise); pin them against each
    # other too by forcing the numpy tier via the module-level lib cache
    if ds._native_cls_lib() is not None:
        native_res = [
            dm.check_pod(p, k, oe)
            for oe in (False, True)
            for k in ("throttle", "clusterthrottle")
            for p in probes
        ]
        old = (ds._cls_lib, ds._cls_lib_tried)
        ds._cls_lib, ds._cls_lib_tried = None, True
        try:
            numpy_res = [
                dm.check_pod(p, k, oe)
                for oe in (False, True)
                for k in ("throttle", "clusterthrottle")
                for p in probes
            ]
        finally:
            ds._cls_lib, ds._cls_lib_tried = old
        assert native_res == numpy_res


def test_limb_compare_extremes():
    """Limb-split compares must hold at int64 extremes and negatives."""
    import jax.numpy as jnp

    from kube_throttler_tpu.ops.check import _limb_gt, _split_limbs

    vals = np.array(
        [0, 1, -1, 2**31, -(2**31), 2**32, -(2**32), 2**62, -(2**62),
         2**63 - 1, -(2**63), 123456789012345, -987654321098765],
        dtype=np.int64,
    )
    a = jnp.asarray(vals)[:, None]
    b = jnp.asarray(vals)[None, :]
    a_hi, a_lo = _split_limbs(a)
    b_hi, b_lo = _split_limbs(b)
    np.testing.assert_array_equal(np.asarray(_limb_gt(a_hi, a_lo, b_hi, b_lo)), vals[:, None] > vals[None, :])


def test_check_pod_host_route_dispatches_nothing(monkeypatch):
    """Up to indexed_check_max affected throttles a single-pod check is
    host arithmetic only: no device kernel runs, on either host tier."""
    plugin, probes = _live_stack()
    dm = plugin.device_manager

    def no_dispatch(*a, **k):
        raise AssertionError("check_pod dispatched a device kernel")

    for name in ("check_pods", "check_pods_compact", "check_pods_gather"):
        monkeypatch.setattr(ds, name, no_dispatch)
    results = [dm.check_pod(p, "throttle") for p in probes]
    assert any(results)
    monkeypatch.setattr(ds, "_cls_lib", None)
    monkeypatch.setattr(ds, "_cls_lib_tried", True)
    assert [dm.check_pod(p, "throttle") for p in probes] == results


def test_prewarm_warms_only_the_gather_rungs():
    """prewarm dispatches the sparse [P,K] batch kernel once a rung a
    kind, and nothing else. (The empty clusterthrottle kind's capacity
    holds no sparse rung.)"""
    plugin, _ = _live_stack()
    dm = plugin.device_manager
    shapes = dm.prewarm()
    assert any(s.startswith("throttle:") for s in shapes), shapes
    for kind in ("throttle", "clusterthrottle"):
        ks = dm._kind(kind)
        for label in (s for s in shapes if s.startswith(f"{kind}:")):
            assert label.startswith(f"{kind}:check_pods_gather[{ks.pcap},"), label
    assert all(s.split(":")[0] in ("throttle", "clusterthrottle") for s in shapes)
    assert len(shapes) == len(set(shapes))
