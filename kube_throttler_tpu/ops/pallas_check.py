"""Pallas TPU kernel for the dense admission sweep.

Why a hand-written kernel: the XLA fusion of the residual-form check streams
the [T,R] throttle tensors from HBM once per pod row (broadcast inputs are
re-read per output tile), so the 100k × 10k sweep is bandwidth-bound at
~200 ms. This kernel tiles the check matrix into [BP × BT] blocks, loads the
pod tile and throttle tile into VMEM ONCE each per block, and does the R
loop entirely on-chip — HBM traffic drops from O(P·T·R) to
O(P·T / BT · R + P·T / BP · R + P·T) (the int8 status output dominates).

64-bit milli values are pre-split into **int32 limb pairs** (hi = v >> 32
signed, lo = low 32 bits biased by 2^31 so signed compare == unsigned
compare); a lexicographic (hi, lo) compare is exactly the s64 compare, in
native int32 VPU ops instead of the X64 rewriter's emulation.

Layout: pod-side arrays [P, R] (pods on sublanes), throttle-side arrays
transposed to [R, T] (throttles on lanes), mask/output [P, T]. R is a
static unrolled loop. P and T must be multiples of the block shape —
callers pad (devicestate capacities and bench shapes already grow in
power-of-two steps).

The kernel consumes the same pod-independent precomputation as
``ops.fastcheck`` (residual form), with the onEqual/step3 variants resolved
to concrete arrays before launch, so the kernel itself has a single static
flag (the step-4 strictness).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .check import (
    CHECK_ACTIVE,
    CHECK_INSUFFICIENT,
    CHECK_NOT_AFFECTED,
    CHECK_NOT_THROTTLED,
    CHECK_POD_EXCEEDS,
)
from .fastcheck import CheckPrecomp
from .schema import PodBatch

BP = 256  # pod rows per block (sublane axis)
BT = 512  # throttle cols per block (lane axis)

# xor bias turning unsigned order into signed; numpy, so importing this
# module builds no device array and initializes no backend
_BIAS = np.int32(-(2**31))


def _split_limbs(x: jnp.ndarray):
    """int64 → (hi int32 signed, lo int32 biased)."""
    hi = (x >> 32).astype(jnp.int32)
    lo = jnp.bitwise_xor((x & 0xFFFFFFFF).astype(jnp.uint32).astype(jnp.int32), _BIAS)
    return hi, lo


def _limb_gt(a_hi, a_lo, b_hi, b_lo):
    """(a > b) for s64 split into (signed hi, biased lo)."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo > b_lo))


def _limb_ge(a_hi, a_lo, b_hi, b_lo):
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def _make_kernel(R: int, on_equal: bool):
    def kernel(
        pod_hi_ref,      # [BP, R] i32
        pod_lo_ref,      # [BP, R] i32
        pod_nz_ref,      # [BP, R] i32 — present ∧ nonzero (0/1)
        thr_hi_ref,      # [R, BT] i32 — threshold (step 1)
        thr_lo_ref,      # [R, BT] i32
        thr_pres_ref,    # [R, BT] i32
        resid_hi_ref,    # [R, BT] i32 — step-4 residual
        resid_lo_ref,    # [R, BT] i32
        st_req_ref,      # [R, BT] i32 — step-2 per-dim flags
        sat_req_ref,     # [R, BT] i32 — step-3 per-dim flags (variant-selected)
        tvec_ref,        # [4, BT] i32 — rows: exceeds_cnt, st|sat cnt, over_cnt, valid
        mask_ref,        # [BP, BT] i8
        out_ref,         # [BP, BT] i8
    ):
        # All predicate logic stays in the i32 (8,128) layout domain; the i8
        # mask input and output cross layouts exactly once each (a supported
        # dtype conversion), avoiding Mosaic i1 relayouts between the (8,128)
        # and (32,128) tilings.
        shape = (BP, BT)
        exceeds = jnp.zeros(shape, dtype=jnp.bool_)
        st_or_sat = jnp.zeros(shape, dtype=jnp.bool_)
        over = jnp.zeros(shape, dtype=jnp.bool_)

        for r in range(R):  # static unroll
            p_hi = pod_hi_ref[:, r][:, None]
            p_lo = pod_lo_ref[:, r][:, None]
            p_nz = pod_nz_ref[:, r][:, None] != 0

            t_pres = thr_pres_ref[r, :][None, :] != 0
            gate = p_nz & t_pres

            t_hi = thr_hi_ref[r, :][None, :]
            t_lo = thr_lo_ref[r, :][None, :]
            exceeds |= gate & _limb_gt(p_hi, p_lo, t_hi, t_lo)

            st_or_sat |= p_nz & (
                (st_req_ref[r, :][None, :] != 0) | (sat_req_ref[r, :][None, :] != 0)
            )

            r_hi = resid_hi_ref[r, :][None, :]
            r_lo = resid_lo_ref[r, :][None, :]
            if on_equal:
                over |= gate & _limb_ge(p_hi, p_lo, r_hi, r_lo)
            else:
                over |= gate & _limb_gt(p_hi, p_lo, r_hi, r_lo)

        exceeds |= tvec_ref[0, :][None, :] != 0
        st_or_sat |= tvec_ref[1, :][None, :] != 0
        over |= tvec_ref[2, :][None, :] != 0
        affected = (mask_ref[:, :].astype(jnp.int32) != 0) & (tvec_ref[3, :][None, :] != 0)

        result = jnp.where(
            exceeds,
            jnp.int32(CHECK_POD_EXCEEDS),
            jnp.where(
                st_or_sat,
                jnp.int32(CHECK_ACTIVE),
                jnp.where(over, jnp.int32(CHECK_INSUFFICIENT), jnp.int32(CHECK_NOT_THROTTLED)),
            ),
        )
        result = jnp.where(affected, result, jnp.int32(CHECK_NOT_AFFECTED))
        out_ref[:, :] = result.astype(jnp.int8)

    return kernel


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal", "interpret"))
def pallas_check_pods(
    pre: CheckPrecomp,
    pods: PodBatch,
    mask: jnp.ndarray,
    on_equal: bool = False,
    step3_on_equal: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Full [P,T] classification via the tiled Pallas kernel.

    P and T must be multiples of (BP, BT); callers pad (encode capacities
    and bench shapes grow in power-of-two steps, so this is a rounding of
    the existing padding, not extra machinery). The limb split and variant
    selection trace into the same jit, so per-call overhead beyond the
    kernel is a handful of cheap elementwise ops.
    """
    P, T = mask.shape
    R = pods.req.shape[1]
    if P % BP or T % BT:
        raise ValueError(f"P={P} and T={T} must be multiples of ({BP},{BT}); pad first")

    pod_hi, pod_lo = _split_limbs(pods.req)
    pod_nz = (pods.req_present & (pods.req != 0) & pods.valid[:, None]).astype(jnp.int32)

    thr_hi, thr_lo = _split_limbs(pre.thr_req.T)
    resid_hi, resid_lo = _split_limbs(pre.resid.T)
    thr_pres = pre.thr_req_present.T.astype(jnp.int32)
    st_req = pre.st_req.T.astype(jnp.int32)
    sat_req = (pre.sat_req_ge if step3_on_equal else pre.sat_req_gt).T.astype(jnp.int32)

    sat_cnt = pre.sat_cnt_ge if step3_on_equal else pre.sat_cnt_gt
    over_cnt = pre.over_cnt_ge if on_equal else pre.over_cnt_gt
    tvec = jnp.stack(
        [
            pre.exceeds_cnt.astype(jnp.int32),
            (pre.st_cnt | sat_cnt).astype(jnp.int32),
            over_cnt.astype(jnp.int32),
            pre.valid.astype(jnp.int32),
        ],
        axis=0,
    )  # [4, T]

    # fold pod validity into the mask: the kernel's pod-independent count
    # flags (tvec) would otherwise classify invalid/padded pod rows whose
    # mask bits are set, diverging from check_pods' NOT_AFFECTED contract
    mask8 = (mask & pods.valid[:, None]).astype(jnp.int8)

    # block indices must be i32 and index maps may not capture constants:
    # with jax_enable_x64 a bare `0` weak-types to i64 (Mosaic fails to
    # legalize the return), so derive an i32 zero from the grid tracers
    pod_spec = pl.BlockSpec((BP, R), lambda i, j: (i, j * 0))
    thr_spec = pl.BlockSpec((R, BT), lambda i, j: (i * 0, j))
    tvec_spec = pl.BlockSpec((4, BT), lambda i, j: (i * 0, j))
    cell_spec = pl.BlockSpec((BP, BT), lambda i, j: (i, j))

    return pl.pallas_call(
        _make_kernel(R, on_equal),
        out_shape=jax.ShapeDtypeStruct((P, T), jnp.int8),
        grid=(P // BP, T // BT),
        in_specs=[
            pod_spec, pod_spec, pod_spec,  # pod hi/lo/nz
            thr_spec, thr_spec, thr_spec,  # thr hi/lo/present
            thr_spec, thr_spec,            # resid hi/lo
            thr_spec, thr_spec,            # st_req, sat_req
            tvec_spec,
            cell_spec,                     # mask
        ],
        out_specs=cell_spec,
        interpret=interpret,
    )(
        pod_hi, pod_lo, pod_nz,
        thr_hi, thr_lo, thr_pres,
        resid_hi, resid_lo,
        st_req, sat_req,
        tvec, mask8,
    )




# runtime retrace budget (KT_JIT_RETRACE_BUDGET): every jit entry here
# reports its compile-cache size per tick — see utils/retrace.py
from ..utils.retrace import register_all as _register_retrace

_register_retrace(globals(), __name__)
