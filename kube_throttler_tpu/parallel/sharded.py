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
from jax import shard_map
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

    used-aggregation is an exact int64 scatter-add over the flat [P·K]
    (col, contribution) pairs (padded/uncounted/out-of-tile slots route to
    an out-of-range index and drop); classification is
    ``check_pods_gather`` against the freshly derived state. Returns the
    same tuple as ``full_update_step``: (counts int32[P,4],
    schedulable bool[P], used_cnt int64[T], used_req int64[T,R],
    st_cnt bool[T], st_req bool[T,R])."""
    from ..ops.check import check_pods_gather

    T = thr_valid.shape[0]
    P_, K = cols.shape
    R = pods.req.shape[1]

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
    used_cnt = jnp.zeros(T, dtype=jnp.int64).at[tgt].add(1, mode="drop")
    # R-LEADING scatter operands: the naive [P·K, R] update-row matrix
    # tile-pads R=8 → 128 lanes on TPU — a 16× expansion (8.6G at the
    # 131072-pod ladder cap), the OOM class the gather kernels once hit.
    # With [R, P·K] rows scattering into an [R, T] accumulator the huge
    # P·K count rides the un-padded lane dim and R the sublane dim;
    # transposing back costs one [T,R].
    req_rows = jnp.broadcast_to(pods.req.T[:, :, None], (R, P_, K)).reshape(R, P_ * K)
    pres_rows = jnp.broadcast_to(
        pods.req_present.T[:, :, None], (R, P_, K)
    ).reshape(R, P_ * K)
    used_req = (
        jnp.zeros((R, T), dtype=jnp.int64).at[:, tgt].add(req_rows, mode="drop").T
    )
    contrib = (
        jnp.zeros((R, T), dtype=jnp.int32)
        .at[:, tgt]
        .add(pres_rows.astype(jnp.int32), mode="drop")
        .T
    )
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
