"""The batched ordered 4-state admission check — the framework's hot kernel.

Reproduces ``check_throttled_for`` (reference throttle_types.go:128-153,
clusterthrottle_types.go:30-55) for every (pod, throttle) pair at once:

    1. pod alone > threshold                  → POD_EXCEEDS (onEqual=False)
    2. persisted status.throttled flags hit   → ACTIVE
    3. used + reserved saturates threshold    → ACTIVE
       (onEqual hardcoded True for Throttle — throttle_types.go:143 —
        caller's flag for ClusterThrottle — clusterthrottle_types.go:45)
    4. used + reserved + pod overflows        → INSUFFICIENT (caller's flag)
    else                                      → NOT_THROTTLED

Presence-mask algebra (absent ≠ zero) follows resource_amount.go:127-159:
a comparison only fires when the dimension is present in BOTH the threshold
and the used side; "blocks this pod" additionally requires the pod to
request that resource non-zero (resource_amount.go:46-65) — except the
pod-count flag, which always blocks.

Shapes: throttle state [T]/[T,R], pods [P]/[P,R], selector mask [P,T].
Everything broadcasts to [P,T,R] inside a single XLA fusion and reduces over
R — no [P,T,R] intermediate is materialized at the default sizes. Three
output forms:

- ``check_pods``          → int8[P,T] full classification (explain path,
  oracle diffing, reason-string formatting for blocked pods);
- ``check_pods_compact``  → int32[P,4] per-pod class counts + bool[P]
  schedulable (dense batch form: 100k×10k never materializes [P,T]);
- ``check_pods_gather``   → same outputs from int32[P,K] matched-cols lists
  instead of a mask: computes P×K×R, the batch path the device manager
  dispatches in production (real masks are sparse — K ≪ T).

The two static booleans (kind asymmetry, caller onEqual) select among 4
compiled variants; shapes are padded so object churn never recompiles.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .schema import PodBatch, ThrottleState

CHECK_NOT_AFFECTED = -1
CHECK_NOT_THROTTLED = 0
CHECK_ACTIVE = 1
CHECK_INSUFFICIENT = 2
CHECK_POD_EXCEEDS = 3

STATUS_NAMES = {
    CHECK_NOT_AFFECTED: "not-affected",
    CHECK_NOT_THROTTLED: "not-throttled",
    CHECK_ACTIVE: "active",
    CHECK_INSUFFICIENT: "insufficient",
    CHECK_POD_EXCEEDS: "pod-requests-exceeds-threshold",
}


def _cmp(u, t, on_equal: bool):
    return u >= t if on_equal else u > t


def _classify_core(
    pod_req, pod_present, pod_nonzero,
    thr_cnt, thr_cnt_present, thr_req, thr_req_present,
    st_cnt_throttled, st_req_flag_present, st_req_throttled,
    au_cnt, au_cnt_present, au_req, au_req_present,
    on_equal: bool, step3_on_equal: bool, axis: int = -1,
):
    """The 4-step ordered resolution on broadcast-compatible operands:
    pod side [P,1(,R)], throttle side [1,T(,R)] (dense) or R-leading
    [R,P,1] / [R,P,K] (gather — see _gather_statuses for why). ``axis``
    names the R dimension of the per-resource operands; the count-side
    operands never carry it. One body ⇒ the dense and sparse kernels
    cannot drift."""
    # --- step 1: pod alone vs threshold (onEqual=False) -------------------
    # pod count is always 1 and always present
    exceeds_cnt = thr_cnt_present & (1 > thr_cnt)
    exceeds_req = jnp.any(
        thr_req_present & pod_present & (pod_req > thr_req) & (pod_req != 0), axis=axis
    )
    exceeds = exceeds_cnt | exceeds_req

    # --- step 2: persisted throttled flags --------------------------------
    st_active = st_cnt_throttled | jnp.any(
        st_req_flag_present & st_req_throttled & pod_nonzero, axis=axis
    )

    # --- step 3: used + reserved saturation -------------------------------
    sat_cnt = thr_cnt_present & au_cnt_present & _cmp(au_cnt, thr_cnt, step3_on_equal)
    sat_req = jnp.any(
        thr_req_present
        & au_req_present
        & _cmp(au_req, thr_req, step3_on_equal)
        & pod_nonzero,
        axis=axis,
    )
    saturated = sat_cnt | sat_req

    # --- step 4: used + reserved + pod overflow ---------------------------
    # pod contributes count 1 (always present) and its requests
    tot_cnt = au_cnt + 1
    tot_req = au_req + pod_req
    tot_req_present = au_req_present | pod_present

    over_cnt = thr_cnt_present & _cmp(tot_cnt, thr_cnt, on_equal)
    over_req = jnp.any(
        thr_req_present
        & tot_req_present
        & _cmp(tot_req, thr_req, on_equal)
        & pod_nonzero,
        axis=axis,
    )
    insufficient = over_cnt | over_req

    # --- ordered resolution ----------------------------------------------
    return jnp.where(
        exceeds,
        jnp.int8(CHECK_POD_EXCEEDS),
        jnp.where(
            st_active | saturated,
            jnp.int8(CHECK_ACTIVE),
            jnp.where(insufficient, jnp.int8(CHECK_INSUFFICIENT), jnp.int8(CHECK_NOT_THROTTLED)),
        ),
    )


def _classify(state: ThrottleState, pods: PodBatch, mask: jnp.ndarray,
              on_equal: bool, step3_on_equal: bool) -> jnp.ndarray:
    """Core classification → int8[P,T]. Static flags pick the variant."""
    # trace-time guard: DimRegistry capacity may have doubled between the
    # throttle-state and pod-batch encodes; fail with an actionable message
    # instead of an opaque XLA broadcast error
    if state.thr_req.shape[1] != pods.req.shape[1]:
        raise ValueError(
            f"resource-dim mismatch: throttle state has R={state.thr_req.shape[1]} "
            f"but pod batch has R={pods.req.shape[1]}; the dim registry grew — "
            "re-encode both against the same capacity"
        )
    if mask.shape != (pods.req.shape[0], state.thr_req.shape[0]):
        raise ValueError(
            f"mask shape {mask.shape} != (P={pods.req.shape[0]}, T={state.thr_req.shape[0]})"
        )
    # pod-side broadcast views: [P,1,R] vs throttle [1,T,R]
    pod_req = pods.req[:, None, :]
    pod_present = pods.req_present[:, None, :]
    pod_nonzero = pod_present & (pod_req != 0)

    result = _classify_core(
        pod_req, pod_present, pod_nonzero,
        state.thr_cnt[None, :], state.thr_cnt_present[None, :],
        state.thr_req[None, :, :], state.thr_req_present[None, :, :],
        state.st_cnt_throttled[None, :],
        state.st_req_flag_present[None, :, :], state.st_req_throttled[None, :, :],
        (state.used_cnt + state.res_cnt)[None, :],
        (state.used_cnt_present | state.res_cnt_present)[None, :],
        (state.used_req + state.res_req)[None, :, :],
        (state.used_req_present | state.res_req_present)[None, :, :],
        on_equal, step3_on_equal,
    )
    affected = mask & state.valid[None, :] & pods.valid[:, None]
    return jnp.where(affected, result, jnp.int8(CHECK_NOT_AFFECTED))


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal"))
def check_pods(state: ThrottleState, pods: PodBatch, mask: jnp.ndarray,
               on_equal: bool = False, step3_on_equal: bool = True) -> jnp.ndarray:
    """Full [P,T] classification (int8)."""
    return _classify(state, pods, mask, on_equal, step3_on_equal)


def statuses_to_compact(statuses: jnp.ndarray):
    """[P,T] statuses → (counts int32[P,4], schedulable bool[P]); the
    schedulable gate mirrors PreFilter (plugin.go:177-180). Shared by every
    compact path so the gate can never silently diverge between kernels."""
    counts = jnp.stack(
        [jnp.sum(statuses == c, axis=1, dtype=jnp.int32) for c in range(4)], axis=1
    )
    schedulable = (
        counts[:, CHECK_ACTIVE] + counts[:, CHECK_INSUFFICIENT] + counts[:, CHECK_POD_EXCEEDS]
    ) == 0
    return counts, schedulable


def _compact(state: ThrottleState, pods: PodBatch, mask: jnp.ndarray,
             on_equal: bool, step3_on_equal: bool):
    return statuses_to_compact(_classify(state, pods, mask, on_equal, step3_on_equal))


def check_step(state: ThrottleState, pods: PodBatch, mask: jnp.ndarray):
    """Un-jitted forward step (PreFilter defaults: onEqual=False, Throttle
    kind) for embedding under an outer jit — returns (counts, schedulable)."""
    return _compact(state, pods, mask, False, True)


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal"))
def check_pods_gather(state: ThrottleState, pods: PodBatch, cols: jnp.ndarray,
                      on_equal: bool = False, step3_on_equal: bool = True):
    """Sparse batch check: ``cols`` int32[P,K] lists each pod's matched
    throttle columns (-1 pads empty slots). Gathers the K throttle rows per
    pod and runs the same 4-step resolution as ``check_pods_compact`` over
    [P,K,R] instead of [P,T,R] — on real clusters each pod matches a
    handful of throttles, so K ≪ T and the batch drops ~T/K× in both FLOPs
    and memory traffic (and needs no [P,T] mask on device at all).

    Returns ``(counts int32[P,4], schedulable bool[P])``, identical to
    ``check_pods_compact`` given a cols/mask pair describing the same
    matches (parity-tested)."""
    if state.thr_req.shape[1] != pods.req.shape[1]:
        raise ValueError(
            f"resource-dim mismatch: throttle state has R={state.thr_req.shape[1]} "
            f"but pod batch has R={pods.req.shape[1]}; the dim registry grew — "
            "re-encode both against the same capacity"
        )
    if cols.ndim != 2 or cols.shape[0] != pods.req.shape[0]:
        raise ValueError(
            f"cols shape {cols.shape} != (P={pods.req.shape[0]}, K)"
        )
    return statuses_to_compact(
        _gather_statuses_blocked(state, pods, cols, on_equal, step3_on_equal)
    )


def _gather_statuses(state, pods, cols, on_equal, step3_on_equal):
    """Shared body of the sparse gather kernels: int8[P,K] per-slot
    statuses (CHECK_NOT_AFFECTED for padded/invalid slots).

    Orientation: the per-resource operands are gathered R-LEADING —
    ``state.thr_req.T[:, c]`` → [R,P,K] — not the naive ``thr_req[c]`` →
    [P,K,R]. TPU tiles the two minor dims (8,128): an R-minor gather
    result pads R=8 → 128 lanes, a 16× memory/bandwidth expansion that
    OOM'd the 100k×10k prewarm on a 16G v5e (4G per gathered u32 operand,
    observed r5). R-leading puts K on the lane dim (pads ≤2× at K=64 and
    not at all from 128 up) and R on the cheap outer dim; the reduction
    over R becomes ``axis=0``."""
    c = jnp.maximum(cols, 0)  # [P,K]; padded slots gather col 0 then mask out
    slot = (cols >= 0) & state.valid[c] & pods.valid[:, None]

    def g(a):  # [T,R] per-resource state → [R,P,K]
        return a.T[:, c]

    pod_req = pods.req.T[:, :, None]  # [R,P,1]
    pod_present = pods.req_present.T[:, :, None]
    pod_nonzero = pod_present & (pod_req != 0)

    result = _classify_core(
        pod_req, pod_present, pod_nonzero,
        state.thr_cnt[c], state.thr_cnt_present[c],
        g(state.thr_req), g(state.thr_req_present),
        state.st_cnt_throttled[c],
        g(state.st_req_flag_present), g(state.st_req_throttled),
        (state.used_cnt + state.res_cnt)[c],
        (state.used_cnt_present | state.res_cnt_present)[c],
        g(state.used_req + state.res_req),
        g(state.used_req_present | state.res_req_present),
        on_equal, step3_on_equal, axis=0,
    )
    return jnp.where(slot, result, jnp.int8(CHECK_NOT_AFFECTED))


# Peak-footprint governor for the sparse gather kernels: a [P,K] dispatch
# materializes ~6 gathered [R,P,K] operands (u32 limbs + presence preds),
# so an unbounded P×K — the 2048-col rung at the 131072-pod ladder cap is
# 2.1G elements — cannot be dispatched as one program on a 16G chip. Blocks
# of ≤ KT_GATHER_CHUNK_ELEMS padded elements (R × P_block × K_padded) run
# under lax.map: one compiled block body, device-serial blocks, bit-
# identical statuses. 64M elems ≈ 256M per u32 operand ≈ ~1.5G peak.
try:
    _GATHER_CHUNK_ELEMS = int(
        os.environ.get("KT_GATHER_CHUNK_ELEMS", str(64 * 1024 * 1024))
    )
except ValueError:
    # a malformed override must not kill module import; fall back to the
    # 64M default
    _GATHER_CHUNK_ELEMS = 64 * 1024 * 1024


def _gather_statuses_blocked(state, pods, cols, on_equal, step3_on_equal):
    """_gather_statuses, chunked over P when the padded gather footprint
    exceeds _GATHER_CHUNK_ELEMS. Shapes are static under jit, so the block
    decomposition is a trace-time decision; P is padded to a whole number
    of blocks with invalid pods (slot masking already yields
    CHECK_NOT_AFFECTED there) and sliced back."""
    P, K = cols.shape
    R = pods.req.shape[1]
    k_pad = max(K, 128)  # lane-dim tile: K below 128 pads up to 128
    if P * k_pad * R <= _GATHER_CHUNK_ELEMS:
        return _gather_statuses(state, pods, cols, on_equal, step3_on_equal)
    pb = max(1, _GATHER_CHUNK_ELEMS // (k_pad * R))
    nb = -(-P // pb)
    pad = nb * pb - P

    def padp(a):
        widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, widths)

    pods_b = PodBatch(
        valid=padp(pods.valid).reshape(nb, pb),
        req=padp(pods.req).reshape(nb, pb, R),
        req_present=padp(pods.req_present).reshape(nb, pb, R),
    )
    cols_b = jnp.pad(cols, ((0, pad), (0, 0)), constant_values=-1).reshape(nb, pb, K)

    def block(xs):
        bpods, bcols = xs
        return _gather_statuses(state, bpods, bcols, on_equal, step3_on_equal)

    out = lax.map(block, (pods_b, cols_b))  # [nb, pb, K] int8
    return out.reshape(nb * pb, K)[:P]


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal"))
def check_pods_gather_statuses(
    state: ThrottleState, pods: PodBatch, cols: jnp.ndarray,
    on_equal: bool = False, step3_on_equal: bool = True,
):
    """``check_pods_gather`` returning the raw int8[P,K] per-slot statuses
    instead of compact counts — the micro-batching pre_filter front-end
    needs each pod's per-throttle classification to build reference reason
    strings (plugin.go:182-214), not just the verdict."""
    return _gather_statuses_blocked(state, pods, cols, on_equal, step3_on_equal)


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal"))
def check_pods_compact(state: ThrottleState, pods: PodBatch, mask: jnp.ndarray,
                       on_equal: bool = False, step3_on_equal: bool = True):
    """Hot-path form: per-pod class counts, no [P,T] materialization.

    Returns ``(counts int32[P,4], schedulable bool[P])`` where counts[p,c]
    is the number of affected throttles classifying pod p as class c
    (NOT_THROTTLED/ACTIVE/INSUFFICIENT/POD_EXCEEDS), and schedulable[p]
    mirrors PreFilter's gate: no active/insufficient/exceeds throttle
    (plugin.go:177-180).
    """
    return _compact(state, pods, mask, on_equal, step3_on_equal)


# runtime retrace budget (KT_JIT_RETRACE_BUDGET): every jit entry here
# reports its compile-cache size per tick — see utils/retrace.py
from ..utils.retrace import register_all as _register_retrace

_register_retrace(globals(), __name__)
