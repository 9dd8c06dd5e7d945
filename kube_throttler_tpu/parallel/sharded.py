"""The full update step — single-device and mesh-sharded variants.

One "step" is a complete system tick, the analog of a training step for this
framework: resolve every throttle's time-varying threshold, re-aggregate
``used`` from the pod set, recompute throttled flags, and classify every
pod × throttle admission cell — i.e. a full reconcile pass fused with a full
PreFilter sweep.

``full_update_step`` is the pure single-shard program. ``sharded_full_update``
wraps it in ``shard_map`` over a ("pods","throttles") mesh: each device owns
a [P/dp, T/tp] tile of the mask, a P/dp slice of pods, and a T/tp slice of
throttle state; the only cross-device traffic is

- ``psum`` over the **pods** axis of the used-aggregation partials
  (each pod shard contributes its masked sums for the local throttle tile);
- ``psum`` over the **throttles** axis of per-pod class counts
  (each throttle tile contributes its verdict counts for the local pods).

Both are single-hop ICI all-reduces of [T_loc,R] / [P_loc,4] tiles — no
[P,T] global tensor ever exists on any device.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dataclasses import fields as _dc_fields

from ..ops.aggregate import aggregate_used, throttled_flags
from ..ops.check import CHECK_ACTIVE, CHECK_INSUFFICIENT, CHECK_POD_EXCEEDS, _classify
from ..ops.overrides import OverrideSchedule, calculate_thresholds
from ..ops.schema import PodBatch, ThrottleState


def uniform_sched_specs(spec) -> OverrideSchedule:
    """OverrideSchedule spec pytree with every leaf on one PartitionSpec.
    Shared by all mesh wrappers (2D dense, 2D sparse, ring) so adding a
    field to OverrideSchedule is a one-place change instead of a silent
    shard_map pytree mismatch in whichever copy was forgotten."""
    return OverrideSchedule(**{f.name: spec for f in _dc_fields(OverrideSchedule)})


def uniform_pods_specs(spec) -> PodBatch:
    """PodBatch spec pytree with every leaf on one PartitionSpec."""
    return PodBatch(**{f.name: spec for f in _dc_fields(PodBatch)})


def full_update_step(
    sched: OverrideSchedule,
    pods: PodBatch,
    mask: jnp.ndarray,  # bool[P,T]
    counted: jnp.ndarray,  # bool[P] — running pods that count into used
    res_cnt: jnp.ndarray,
    res_cnt_present: jnp.ndarray,
    res_req: jnp.ndarray,
    res_req_present: jnp.ndarray,
    thr_valid: jnp.ndarray,  # bool[T]
    now_ns: jnp.ndarray,
    *,
    on_equal: bool = False,
    step3_on_equal: bool = True,
    pod_axis: str | None = None,
    thr_axis: str | None = None,
):
    """One full tick. With ``pod_axis``/``thr_axis`` set (inside shard_map),
    partial reductions are psum-ed across the mesh.

    Returns (counts int32[P,4], schedulable bool[P],
             used_cnt int64[T], used_req int64[T,R],
             st_cnt bool[T], st_req bool[T,R]).
    """
    # 1. time-varying thresholds (local throttle tile)
    thr_cnt, thr_cnt_present, thr_req, thr_req_present = calculate_thresholds(
        sched, now_ns
    )

    # 2. used aggregation: local pod-shard partial, then sum across pod shards
    used_cnt, used_req, contrib = aggregate_used(pods, mask, counted)
    if pod_axis is not None:
        used_cnt = jax.lax.psum(used_cnt, pod_axis)
        used_req = jax.lax.psum(used_req, pod_axis)
        contrib = jax.lax.psum(contrib, pod_axis)
    used_cnt_present = used_cnt > 0
    used_req_present = contrib > 0

    # 3. status.throttled flags (reconcile's onEqual=True compare)
    st_cnt, st_req, st_req_flag_present = throttled_flags(
        thr_cnt, thr_cnt_present, thr_req, thr_req_present,
        used_cnt, used_cnt_present, used_req, used_req_present,
    )

    # 4. admission classification against the fresh state
    state = ThrottleState(
        valid=thr_valid,
        thr_cnt=thr_cnt,
        thr_cnt_present=thr_cnt_present,
        thr_req=thr_req,
        thr_req_present=thr_req_present,
        used_cnt=used_cnt,
        used_cnt_present=used_cnt_present,
        used_req=used_req,
        used_req_present=used_req_present,
        res_cnt=res_cnt,
        res_cnt_present=res_cnt_present,
        res_req=res_req,
        res_req_present=res_req_present,
        st_cnt_throttled=st_cnt,
        st_req_throttled=st_req,
        st_req_flag_present=st_req_flag_present,
    )
    statuses = _classify(state, pods, mask, on_equal, step3_on_equal)  # int8[P,T]

    # 5. per-pod verdicts: count classes over the local throttle tile, then
    # sum across throttle shards
    counts = jnp.stack(
        [jnp.sum(statuses == c, axis=1, dtype=jnp.int32) for c in range(4)], axis=1
    )
    if thr_axis is not None:
        counts = jax.lax.psum(counts, thr_axis)
    schedulable = (
        counts[:, CHECK_ACTIVE] + counts[:, CHECK_INSUFFICIENT] + counts[:, CHECK_POD_EXCEEDS]
    ) == 0

    return counts, schedulable, used_cnt, used_req, st_cnt, st_req


# Slots per row of the segmented sums: one vector register's lanes.
_LANES = 128


def _limbs(x: jnp.ndarray) -> jnp.ndarray:
    """int64[P,R] → int32[P,3R]: bits 0-15, bits 16-31 (both ≥ 0) and the
    signed high word. Lane sums of the 16-bit limbs cannot overflow int32;
    the high word's wrap only drops multiples of 2^64 once it is shifted
    into place, so ``_unlimb`` of the limb sums is the int64 sum."""
    return jnp.concatenate([x & 0xFFFF, (x >> 16) & 0xFFFF, x >> 32], axis=1).astype(jnp.int32)


def _unlimb(s: jnp.ndarray) -> jnp.ndarray:
    """int32 sums [3R, …] of ``_limbs`` columns → int64 sums [R, …]."""
    a, b, h = (v.astype(jnp.int64) for v in jnp.split(s, 3))
    return a + (b << 16) + (h << 32)


def _used_by_target(pods: PodBatch, tgt: jnp.ndarray, T: int):
    """(used_cnt int64[T], used_req int64[T,R], contrib int32[T,R]) of
    the flat [P·K] slots whose throttle is ``tgt`` (T: the slot counts
    nowhere), as sums over segments of the slots sorted by target.

    One sort orders the slots by target, each carrying its pod, and a
    binary search finds each target's segment [b[t], b[t+1]) of them, so
    its count is b[t+1] − b[t]. One row gather reads each sorted slot's
    pod record (``_limbs`` of its requests, its presence bits), turned
    so the slots ride the lanes and cut into rows of _LANES slots. A
    target's sum is S(b[t+1]) − S(b[t]), where S(b), the sum of the
    slots before b, is the int64 prefix sum of the whole rows before
    b's row plus a masked sum of b's own row. Lane sums stay in int32
    limbs; only the [R, rows] and [R, T] sums are int64. Slots at T sort
    past b[T] and reach no segment. Integer adds wrap, so each
    difference is the segment's sum modulo 2^64 (2^32 for the int32
    presence counts): bit for bit what scatter-adding the slots gives,
    overflow included. Nothing is kept between calls."""
    P_, R = pods.req.shape
    n = tgt.shape[0]
    tgt, pod = lax.sort((tgt, lax.iota(jnp.int32, n) // (n // P_)), num_keys=1)
    bounds = jnp.searchsorted(tgt, lax.iota(jnp.int32, T + 1))

    record = jnp.concatenate(
        [_limbs(pods.req), pods.req_present.astype(jnp.int32)], axis=1
    )  # int32[P, 4R]
    rows = -(-n // _LANES)
    x = jnp.take(record, pod, axis=0, mode="clip").T  # [4R, n]
    x = jnp.pad(x, ((0, 0), (0, rows * _LANES - n))).reshape(4 * R, rows, _LANES)
    row = jnp.minimum(bounds // _LANES, rows - 1)  # b = n ends the last row
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    head = (bounds - row * _LANES)[None, :, None]
    part = jnp.take(x, row, axis=1, mode="clip")  # [4R, T+1, _LANES]
    part = jnp.sum(jnp.where(lane < head, part, 0), axis=2, dtype=jnp.int32)
    whole = jnp.sum(x, axis=2, dtype=jnp.int32)  # [4R, rows]

    def segment_sums(whole, part):  # [C, rows], [C, T+1] → [C, T]
        before = lax.cumsum(whole, axis=1) - whole
        at = jnp.take(before, row, axis=1, mode="clip") + part
        return at[:, 1:] - at[:, :-1]

    used_cnt = (bounds[1:] - bounds[:-1]).astype(jnp.int64)
    used_req = segment_sums(_unlimb(whole[:3 * R]), _unlimb(part[:3 * R])).T
    contrib = segment_sums(whole[3 * R:], part[3 * R:]).T
    return used_cnt, used_req, contrib


@partial(jax.jit, static_argnames=("on_equal", "step3_on_equal", "pod_axis", "thr_axis"))
def full_update_step_gather(
    sched: OverrideSchedule,
    pods: PodBatch,
    cols: jnp.ndarray,  # int32[P,K] matched throttle cols per pod, -1 pads
    counted: jnp.ndarray,  # bool[P]
    res_cnt: jnp.ndarray,
    res_cnt_present: jnp.ndarray,
    res_req: jnp.ndarray,
    res_req_present: jnp.ndarray,
    thr_valid: jnp.ndarray,  # bool[T]
    now_ns: jnp.ndarray,
    *,
    on_equal: bool = False,
    step3_on_equal: bool = True,
    pod_axis: str | None = None,
    thr_axis: str | None = None,
):
    """The SPARSE tick: same fused reconcile+classify as
    ``full_update_step`` but driven by the [P,K] matched-cols companion
    instead of the dense [P,T] mask — O(P·K·R) work and no [P,T] tensor
    anywhere (neither compute nor transfer). On real clusters K ≪ T, so
    this is the production serving shape on one chip AND on a mesh (see
    ``sharded_full_update_gather``).

    Sharded form (``pod_axis``/``thr_axis`` set, inside shard_map): pods
    and their cols rows are sharded over "pods"; cols carry GLOBAL col
    ids, and each "throttles"-axis shard rebases them into its local tile
    (out-of-tile slots → -1, exactly the ownership-partition trick of
    ``sharded_apply_deltas``). used partials psum over the pods axis;
    per-pod class counts psum over the throttles axis (each global col has
    exactly one owning tile, so every slot is counted once). Identical
    comm shape to the dense ``sharded_full_update`` — two single-hop ICI
    all-reduces — with O(P·K) tiles instead of O(P·T).

    used-aggregation is exact in int64: the flat [P·K] slots
    (padded/uncounted/out-of-tile ones routed to the out-of-range target
    T) are sorted by target once, and each throttle's count and sums are
    differences of prefix sums at its segment's bounds
    (``_used_by_target``); classification is
    ``check_pods_gather`` against the freshly derived state. Returns the
    same tuple as ``full_update_step``: (counts int32[P,4],
    schedulable bool[P], used_cnt int64[T], used_req int64[T,R],
    st_cnt bool[T], st_req bool[T,R])."""
    from ..ops.check import check_pods_gather

    T = thr_valid.shape[0]

    if thr_axis is not None:
        # rebase global col ids into this shard's tile; foreign slots pad
        offset = jax.lax.axis_index(thr_axis) * T
        local = (cols >= offset) & (cols < offset + T)
        cols = jnp.where(local, cols - offset, jnp.int32(-1))

    thr_cnt, thr_cnt_present, thr_req, thr_req_present = calculate_thresholds(
        sched, now_ns
    )

    slot = (cols >= 0) & (counted & pods.valid)[:, None]  # [P,K]
    tgt = jnp.where(slot, cols, T).reshape(-1)  # T = out of range ⇒ dropped
    used_cnt, used_req, contrib = _used_by_target(pods, tgt, T)
    if pod_axis is not None:
        used_cnt = jax.lax.psum(used_cnt, pod_axis)
        used_req = jax.lax.psum(used_req, pod_axis)
        contrib = jax.lax.psum(contrib, pod_axis)
    used_cnt_present = used_cnt > 0
    used_req_present = contrib > 0

    st_cnt, st_req, st_req_flag_present = throttled_flags(
        thr_cnt, thr_cnt_present, thr_req, thr_req_present,
        used_cnt, used_cnt_present, used_req, used_req_present,
    )

    state = ThrottleState(
        valid=thr_valid,
        thr_cnt=thr_cnt,
        thr_cnt_present=thr_cnt_present,
        thr_req=thr_req,
        thr_req_present=thr_req_present,
        used_cnt=used_cnt,
        used_cnt_present=used_cnt_present,
        used_req=used_req,
        used_req_present=used_req_present,
        res_cnt=res_cnt,
        res_cnt_present=res_cnt_present,
        res_req=res_req,
        res_req_present=res_req_present,
        st_cnt_throttled=st_cnt,
        st_req_throttled=st_req,
        st_req_flag_present=st_req_flag_present,
    )
    counts, schedulable = check_pods_gather(
        state, pods, cols, on_equal=on_equal, step3_on_equal=step3_on_equal
    )
    if thr_axis is not None:
        # local counts cover only this tile's cols; sum across tiles and
        # re-derive the gate from the GLOBAL counts (mirrors the dense
        # full_update_step's step 5)
        counts = jax.lax.psum(counts, thr_axis)
        schedulable = (
            counts[:, CHECK_ACTIVE]
            + counts[:, CHECK_INSUFFICIENT]
            + counts[:, CHECK_POD_EXCEEDS]
        ) == 0
    return counts, schedulable, used_cnt, used_req, st_cnt, st_req


def sharded_full_update_gather(
    mesh: Mesh, *, on_equal: bool = False, step3_on_equal: bool = True
):
    """Compile the SPARSE full step over a ("pods","throttles") mesh via
    shard_map — the multi-chip serving path without any [P,T] tensor.

    Input layout: pod-side arrays AND the [P,K] global-id cols sharded on
    "pods" (cols replicate over the throttles axis; each shard rebases
    into its tile), throttle-side arrays on "throttles". Outputs: per-pod
    on "pods", per-throttle on "throttles". Comm shape identical to the
    dense ``sharded_full_update`` (two psums); per-device compute and
    memory drop from O(P·T/(dp·tp)) to O(P·K/dp)."""
    pod_spec = P("pods")
    thr_spec = P("throttles")

    sched_specs = uniform_sched_specs(thr_spec)
    pods_specs = uniform_pods_specs(pod_spec)

    def _step(sched, pods, cols, counted, res_cnt, res_cnt_p, res_req, res_req_p, thr_valid, now_ns):
        # the raw body (like the dense wrapper calls unjitted
        # full_update_step): shard_map provides the axis context
        return full_update_step_gather.__wrapped__(
            sched, pods, cols, counted,
            res_cnt, res_cnt_p, res_req, res_req_p, thr_valid, now_ns,
            on_equal=on_equal, step3_on_equal=step3_on_equal,
            pod_axis="pods", thr_axis="throttles",
        )

    mapped = shard_map(
        _step,
        mesh=mesh,
        in_specs=(
            sched_specs, pods_specs, pod_spec, pod_spec,
            thr_spec, thr_spec, thr_spec, thr_spec, thr_spec, P(),
        ),
        out_specs=(pod_spec, pod_spec, thr_spec, thr_spec, thr_spec, thr_spec),
    )
    return jax.jit(mapped)


def sharded_apply_deltas(mesh: Mesh):
    """Streaming reconcile (BASELINE cfg5) over a throttle-sharded mesh.

    The used-aggregates live tiled over the mesh's ``throttles`` axis —
    each device owns agg rows [T/tp] — and a batch of pod-churn deltas
    (global throttle ids) is REPLICATED to every device: each shard
    rebases ids into its tile (global id − tile offset) and scatter-adds
    only the rows it owns, dropping the rest (``mode="drop"``). No
    collective is needed at all — scatter targets partition exactly by
    ownership, so the update is embarrassingly parallel across shards;
    reads (gathers for status writes) stay tile-local too.

    Returns a jitted fn
    ``(used_cnt[T], used_req[T,R], contrib[T,R], ids[N,K], sign[N,K],
    pod_req[N,R], pod_present[N,R]) → (used_cnt, used_req, contrib)``
    with the agg arrays sharded on "throttles" and deltas replicated.
    Exactness: scatter-adds commute in int64, and each global id lands in
    exactly one tile, so the result is bit-identical to the single-device
    ``apply_pod_deltas_batched`` (property-tested on the 8-device mesh).
    """
    from ..ops.aggregate import apply_pod_deltas_batched

    thr_spec = P("throttles")

    def _apply(used_cnt, used_req, contrib, ids, sign, pod_req, pod_present):
        t_local = used_cnt.shape[0]  # tile rows (shard_map sees the local view)
        idx = jax.lax.axis_index("throttles")
        offset = idx * t_local
        local_ids = jnp.where(
            (ids >= offset) & (ids < offset + t_local), ids - offset, t_local
        ).astype(ids.dtype)  # out-of-tile → t_local → dropped by the scatter
        return apply_pod_deltas_batched(
            used_cnt, used_req, contrib, local_ids, sign, pod_req, pod_present
        )

    mapped = shard_map(
        _apply,
        mesh=mesh,
        in_specs=(thr_spec, thr_spec, thr_spec, P(), P(), P(), P()),
        out_specs=(thr_spec, thr_spec, thr_spec),
    )
    return jax.jit(mapped)


def sharded_full_update(mesh: Mesh, *, on_equal: bool = False, step3_on_equal: bool = True):
    """Compile the full step over a ("pods","throttles") mesh via shard_map.

    Input layout: pod-side arrays sharded on "pods", throttle-side (override
    schedule, reservations, validity) on "throttles", the mask on both.
    Outputs: per-pod arrays sharded on "pods"; per-throttle on "throttles".
    """
    pod_spec = P("pods")
    thr_spec = P("throttles")

    sched_specs = uniform_sched_specs(thr_spec)
    pods_specs = uniform_pods_specs(pod_spec)

    def _step(sched, pods, mask, counted, res_cnt, res_cnt_p, res_req, res_req_p, thr_valid, now_ns):
        return full_update_step(
            sched, pods, mask, counted,
            res_cnt, res_cnt_p, res_req, res_req_p, thr_valid, now_ns,
            on_equal=on_equal, step3_on_equal=step3_on_equal,
            pod_axis="pods", thr_axis="throttles",
        )

    mapped = shard_map(
        _step,
        mesh=mesh,
        in_specs=(
            sched_specs, pods_specs, P("pods", "throttles"), pod_spec,
            thr_spec, thr_spec, thr_spec, thr_spec, thr_spec, P(),
        ),
        out_specs=(pod_spec, pod_spec, thr_spec, thr_spec, thr_spec, thr_spec),
    )
    return jax.jit(mapped)
