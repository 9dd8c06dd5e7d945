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
  instead of a mask: one row gather of a packed per-throttle record per
  slot, the batch path the device manager dispatches in production (real
  masks are sparse — K ≪ T).

The two static booleans (kind asymmetry, caller onEqual) select among 4
compiled variants; shapes are padded so object churn never recompiles.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
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
    on_equal: bool, step3_on_equal: bool,
):
    """The 4-step check on broadcast-compatible operands: pod side
    [P,1(,R)], throttle side [1,T(,R)], reduced over the trailing R."""
    # --- step 1: pod alone vs threshold (onEqual=False) -------------------
    # pod count is always 1 and always present
    exceeds_cnt = thr_cnt_present & (1 > thr_cnt)
    exceeds_req = jnp.any(
        thr_req_present & pod_present & (pod_req > thr_req) & (pod_req != 0), axis=-1
    )
    exceeds = exceeds_cnt | exceeds_req

    # --- step 2: persisted throttled flags --------------------------------
    st_active = st_cnt_throttled | jnp.any(
        st_req_flag_present & st_req_throttled & pod_nonzero, axis=-1
    )

    # --- step 3: used + reserved saturation -------------------------------
    sat_cnt = thr_cnt_present & au_cnt_present & _cmp(au_cnt, thr_cnt, step3_on_equal)
    sat_req = jnp.any(
        thr_req_present
        & au_req_present
        & _cmp(au_req, thr_req, step3_on_equal)
        & pod_nonzero,
        axis=-1,
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
        axis=-1,
    )
    insufficient = over_cnt | over_req
    return _resolve(exceeds, st_active | saturated, insufficient)


def _resolve(exceeds, active, insufficient):
    """The ordered resolution, shared by the dense and sparse kernels."""
    return jnp.where(
        exceeds,
        jnp.int8(CHECK_POD_EXCEEDS),
        jnp.where(
            active,
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
    throttle columns (-1 pads empty slots). Gathers the K throttle records
    per pod and runs the same 4-step resolution as ``check_pods_compact``
    over [P,K] slots instead of [P,T] cells — on real clusters each pod
    matches a handful of throttles, so K ≪ T and the batch drops ~T/K× in
    both FLOPs and memory traffic (and needs no [P,T] mask on device at
    all).

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


class CheckPrecomp(NamedTuple):
    """Pod-independent per-throttle tensors of the residual form.

    Every addition in steps 3-4 involves only pod-independent terms, so

        used + reserved + pod  >  threshold
    ⟺  pod  >  threshold - (used + reserved)          (exact in int64)

    and step 3 (``used + reserved`` vs threshold) has no pod term at all.
    The saturation flags for both onEqual variants, the step-4 residual
    and the count verdicts (a pod's count contribution is always exactly
    1) are computed once per state, leaving the per-slot work to compares.
    ``thr - (used + reserved)`` cannot overflow for any state this
    framework produces: used/reserved are sums of non-negative pod
    amounts, and thresholds are admission-scale quantities ≪ 2^62."""

    valid: jnp.ndarray  # bool[T]
    thr_req: jnp.ndarray  # int64[T,R] — step-1 compare target
    thr_req_present: jnp.ndarray  # bool[T,R]
    exceeds_cnt: jnp.ndarray  # bool[T] — 1 > thr_cnt (step 1, onEqual=False)
    st_cnt: jnp.ndarray  # bool[T] — status.throttled count flag
    st_req: jnp.ndarray  # bool[T,R] — status.throttled request flag ∧ present
    sat_cnt_ge: jnp.ndarray  # bool[T] — step-3 count, onEqual=True
    sat_cnt_gt: jnp.ndarray  # bool[T] — step-3 count, onEqual=False
    sat_req_ge: jnp.ndarray  # bool[T,R]
    sat_req_gt: jnp.ndarray  # bool[T,R]
    resid: jnp.ndarray  # int64[T,R] — thr - (used+reserved), step-4 target
    over_cnt_ge: jnp.ndarray  # bool[T] — step-4 count, onEqual=True
    over_cnt_gt: jnp.ndarray  # bool[T]


@jax.jit
def precompute_check_state(state: ThrottleState) -> CheckPrecomp:
    au_cnt = state.used_cnt + state.res_cnt
    au_cnt_present = state.used_cnt_present | state.res_cnt_present
    au_req = state.used_req + state.res_req
    au_req_present = state.used_req_present | state.res_req_present

    sat_cnt_base = state.thr_cnt_present & au_cnt_present
    sat_req_base = state.thr_req_present & au_req_present

    return CheckPrecomp(
        valid=state.valid,
        thr_req=state.thr_req,
        thr_req_present=state.thr_req_present,
        exceeds_cnt=state.thr_cnt_present & (1 > state.thr_cnt),
        st_cnt=state.st_cnt_throttled,
        st_req=state.st_req_flag_present & state.st_req_throttled,
        sat_cnt_ge=sat_cnt_base & (au_cnt >= state.thr_cnt),
        sat_cnt_gt=sat_cnt_base & (au_cnt > state.thr_cnt),
        sat_req_ge=sat_req_base & (au_req >= state.thr_req),
        sat_req_gt=sat_req_base & (au_req > state.thr_req),
        resid=state.thr_req - au_req,
        # step-4 count: total count = au_cnt + 1, always present
        over_cnt_ge=state.thr_cnt_present & (au_cnt + 1 >= state.thr_cnt),
        over_cnt_gt=state.thr_cnt_present & (au_cnt + 1 > state.thr_cnt),
    )


# xor bias turning unsigned order into signed; numpy, so importing this
# module builds no device array and initializes no backend
_BIAS = np.int32(-(2**31))


def _split_limbs(x: jnp.ndarray):
    """int64 → (hi int32 signed, lo int32 biased): hi = x >> 32, lo = the
    low 32 bits biased by 2^31 so that a signed compare of lo is the
    unsigned one. A lexicographic (hi, lo) compare is then exactly the
    s64 compare, in native int32 ops instead of the X64 rewriter's
    emulation."""
    hi = (x >> 32).astype(jnp.int32)
    lo = jnp.bitwise_xor((x & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32), _BIAS)
    return hi, lo


def _limb_gt(a_hi, a_lo, b_hi, b_lo):
    """(a > b) for s64 split into (signed hi, biased lo)."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo > b_lo))


# Flag bits of a gathered record's first word.
_F_VALID, _F_EXCEEDS_CNT, _F_ACTIVE_CNT, _F_OVER_CNT = 1, 2, 4, 8
_INT64_MAX = jnp.iinfo(jnp.int64).max
_INT64_MIN = jnp.iinfo(jnp.int64).min


def _bitmask(bits):
    """bool[N,R] → int32[N, ceil(R/32)], bit r%32 of word r//32."""
    n, r = bits.shape
    nw = -(-r // 32)
    b = jnp.pad(bits, ((0, 0), (0, nw * 32 - r))).reshape(n, nw, 32).astype(jnp.uint32)
    words = jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32)


def _gather_table(state, on_equal, step3_on_equal):
    """The pod-independent record of each throttle, int32[T, 1 + nw + 4R]
    (nw = ceil(R/32)), in the residual form of ``CheckPrecomp``:

    - word 0, flags: valid, step-1 count exceeds, step-2/3 count active
      (``step3_on_equal`` variant), step-4 count over (``on_equal``);
    - nw words, per dim: step-2 flag or step-3 saturation;
    - per dim, the step-1 target ``thr_req`` and the step-4 target
      ``thr − (used + reserved)`` (less 1 under ``on_equal``, so both
      compares are strict; exact, as the residual cannot be int64 min
      for any state ``CheckPrecomp``'s overflow note admits), each as (hi,
      lo) int32 limbs, hi and lo planes of R words each. An absent
      threshold dim holds int64 max, which no pod request exceeds.
    """
    pre = precompute_check_state(state)
    sat_cnt = pre.sat_cnt_ge if step3_on_equal else pre.sat_cnt_gt
    sat_req = pre.sat_req_ge if step3_on_equal else pre.sat_req_gt
    over_cnt = pre.over_cnt_ge if on_equal else pre.over_cnt_gt
    flags = sum(
        b.astype(jnp.int32) * bit
        for b, bit in ((pre.valid, _F_VALID), (pre.exceeds_cnt, _F_EXCEEDS_CNT),
                       (pre.st_cnt | sat_cnt, _F_ACTIVE_CNT), (over_cnt, _F_OVER_CNT))
    )
    resid = pre.resid - 1 if on_equal else pre.resid
    thr_hi, thr_lo = _split_limbs(jnp.where(pre.thr_req_present, pre.thr_req, _INT64_MAX))
    res_hi, res_lo = _split_limbs(jnp.where(pre.thr_req_present, resid, _INT64_MAX))
    return jnp.concatenate(
        [flags[:, None], _bitmask(pre.st_req | sat_req), thr_hi, thr_lo, res_hi, res_lo],
        axis=1,
    )


def _pod_rows(pods):
    """The pod side of the sparse check: (valid bool[P], request limbs
    (hi, lo) int32[P,R] each, nonzero-dims bitmask int32[P,nw]). A dim the
    pod does not request non-zero holds int64 min, which exceeds nothing."""
    nonzero = pods.req_present & (pods.req != 0)
    hi, lo = _split_limbs(jnp.where(nonzero, pods.req, _INT64_MIN))
    return pods.valid, hi, lo, _bitmask(nonzero)


def _classify_slots(table, valid, hi, lo, bits, cols):
    """int8[P,K] statuses of pods (valid, hi, lo, bits) against the table
    rows their cols name: one row gather per slot, then compares of the
    pod's own limbs against the gathered targets. Flag and bitmask tests
    are lane-masked reductions over the whole record, so no one-lane
    slice of it (a 128-lane tile per word) is materialized."""
    P, K = cols.shape
    R, W = hi.shape[1], table.shape[1]
    o = 1 + bits.shape[1]
    g = jnp.take(table, jnp.maximum(cols, 0).reshape(-1), axis=0, mode="clip")
    g = g.reshape(P, K, W)

    def shares_bits(row):  # row int32[P or 1, ≤W], zero-extended to W lanes
        row = jnp.pad(row, ((0, 0), (0, W - row.shape[1])))
        return jnp.any((g & row[:, None, :]) != 0, axis=-1)

    def flag(bit):
        return shares_bits(jnp.full((1, 1), bit, dtype=jnp.int32))

    def limb_any(i):  # pod > the i-th target block, in any dim
        return jnp.any(_limb_gt(hi[:, None, :], lo[:, None, :],
                                g[:, :, o + 2 * i * R:o + (2 * i + 1) * R],
                                g[:, :, o + (2 * i + 1) * R:o + (2 * i + 2) * R]), axis=-1)

    active_row = jnp.concatenate(
        [jnp.full((P, 1), _F_ACTIVE_CNT, dtype=jnp.int32), bits], axis=1
    )
    exceeds = flag(_F_EXCEEDS_CNT) | limb_any(0)
    active = shares_bits(active_row)
    insufficient = flag(_F_OVER_CNT) | limb_any(1)
    slot = (cols >= 0) & flag(_F_VALID) & valid[:, None]
    return jnp.where(
        slot, _resolve(exceeds, active, insufficient), jnp.int8(CHECK_NOT_AFFECTED)
    )


def _gather_statuses(state, pods, cols, on_equal, step3_on_equal):
    """Shared body of the sparse gather kernels: int8[P,K] per-slot
    statuses (CHECK_NOT_AFFECTED for padded/invalid slots).

    Each slot reads its throttle's whole record with one row gather, like
    an embedding lookup, where gathering the raw state planes element by
    element took 16 gathers a slot (81 ns a slot on a v5e at 100k pods ×
    10k throttles, against 7 ns). The record is built once per call, in
    the same jit, from the state."""
    table = _gather_table(state, on_equal, step3_on_equal)
    return _classify_slots(table, *_pod_rows(pods), cols)


# Peak-footprint governor for the sparse gather kernels: a [P,K] dispatch
# materializes the gathered records, one row per slot, each padded to a
# whole number of 128-lane tiles, so an unbounded P×K — the 2048-col rung
# at the 131072-pod ladder cap is 268M rows, 137G as int32 — cannot be
# dispatched as one program on a 16G chip. Blocks of ≤
# KT_GATHER_CHUNK_ELEMS padded elements (P_block × K × 128) run under
# lax.map: one compiled block body, device-serial blocks, bit-identical
# statuses. 64M elems ≈ 256M per gathered block.
try:
    _GATHER_CHUNK_ELEMS = int(
        os.environ.get("KT_GATHER_CHUNK_ELEMS", str(64 * 1024 * 1024))
    )
except ValueError:
    # a malformed override must not kill module import; fall back to the
    # 64M default
    _GATHER_CHUNK_ELEMS = 64 * 1024 * 1024


def _gather_statuses_blocked(state, pods, cols, on_equal, step3_on_equal):
    """_gather_statuses, chunked over P when the padded footprint of the
    gathered records exceeds _GATHER_CHUNK_ELEMS. Shapes are static under
    jit, so the block decomposition is a trace-time decision; the table
    is built once, P is padded to a whole number of blocks with invalid
    pods (slot masking already yields CHECK_NOT_AFFECTED there) and sliced
    back."""
    P, K = cols.shape
    table = _gather_table(state, on_equal, step3_on_equal)
    rows = _pod_rows(pods)
    lanes = -(-table.shape[1] // 128) * 128
    if P * K * lanes <= _GATHER_CHUNK_ELEMS:
        return _classify_slots(table, *rows, cols)
    pb = max(1, _GATHER_CHUNK_ELEMS // (K * lanes))
    nb = -(-P // pb)
    pad = nb * pb - P

    def blocks(a, fill=0):
        widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill).reshape((nb, pb) + a.shape[1:])

    out = lax.map(
        lambda xs: _classify_slots(table, *xs),
        tuple(blocks(a) for a in rows) + (blocks(cols, -1),),
    )  # [nb, pb, K] int8
    return out.reshape(nb * pb, K)[:P]


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
