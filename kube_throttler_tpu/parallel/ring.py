"""Ring-rotation full sweep — the ring-attention/context-parallel pattern
mapped onto the (pods × throttles) check matrix.

In `sharded.py` the mesh is 2D and each device holds a [P/dp, T/tp] mask
tile; the cross-device traffic is two `psum`s. This module is the
alternative decomposition for when the **throttle-side state dominates
memory** (huge T×R threshold/override/used tensors, the analog of long-KV
in ring attention): a 1D ring where

- every device *permanently owns* one T/n throttle tile (thresholds,
  override schedule, reservations, used accumulators) and its mask columns
  ``mask[:, T_loc]`` — throttle state never moves;
- pod blocks ([P/n, R] requests + validity) *rotate* around the ring via
  `ppermute`, exactly like KV blocks in ring attention — hop s delivers the
  block owned by device (me − s) mod n;
- sweep 1 accumulates each tile's ``used`` from every visiting pod block
  (after n hops every tile has the full sum — a ring all-reduce that never
  materializes a global [P,T] or [T,R] tensor anywhere);
- thresholds + throttled flags are then computed tile-locally;
- sweep 2 rotates the blocks again, now carrying [P/n, 4] verdict-count
  accumulators with them; each device classifies the visiting block against
  its tile, and after n hops the counts arrive home complete.

Per-hop traffic is O(P/n · R) — independent of T — and all hops are
neighbor `ppermute`s that ride ICI. Output layout matches
``sharded_full_update`` so callers can swap decompositions freely.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.aggregate import throttled_flags
from ..ops.check import CHECK_ACTIVE, CHECK_INSUFFICIENT, CHECK_POD_EXCEEDS, _classify
from ..ops.overrides import OverrideSchedule, calculate_thresholds
from ..ops.schema import PodBatch, ThrottleState

AXIS = "ring"


def ring_full_update(mesh: Mesh, *, on_equal: bool = False, step3_on_equal: bool = True):
    """Compile the full tick over a 1D ("ring",) mesh.

    Input layout (per-device shards in parentheses):
      pods, counted      — sharded on the ring        ([P/n], [P/n,R])
      mask               — [P, T] sharded on axis 1   ([P, T/n] columns)
      sched, reservations, thr_valid — sharded on the ring ([T/n, ...])
      now_ns             — replicated
    Outputs mirror ``sharded_full_update``: per-pod arrays ring-sharded,
    per-throttle arrays ring-sharded.
    """
    assert mesh.axis_names == (AXIS,), f"ring mesh must have a single '{AXIS}' axis"
    n = mesh.devices.size
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _rotate(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, AXIS, perm), tree
        )

    def _sweep(sched, pods, mask_cols, counted,
               res_cnt, res_cnt_p, res_req, res_req_p, thr_valid, now_ns):
        me = jax.lax.axis_index(AXIS)
        p_loc = pods.valid.shape[0]
        t_loc = mask_cols.shape[1]
        r = pods.req.shape[1]

        # ---- sweep 1: ring all-reduce of used into the resident tile
        used_cnt = jnp.zeros(t_loc, dtype=jnp.int64)
        used_req = jnp.zeros((t_loc, r), dtype=jnp.int64)
        contrib = jnp.zeros((t_loc, r), dtype=jnp.int32)
        blk = (pods, counted)
        for s in range(n):
            origin = (me - s) % n
            start = (origin * p_loc).astype(jnp.int32)
            m = jax.lax.dynamic_slice(mask_cols, (start, jnp.int32(0)), (p_loc, t_loc))
            bpods, bcounted = blk
            mm = m & bcounted[:, None]  # [P/n, T/n]
            used_cnt = used_cnt + jnp.sum(mm, axis=0, dtype=jnp.int64)
            mb = mm[:, :, None]
            used_req = used_req + jnp.sum(
                jnp.where(mb, bpods.req[:, None, :], 0), axis=0
            )
            contrib = contrib + jnp.sum(
                (mb & bpods.req_present[:, None, :]).astype(jnp.int32), axis=0
            )
            if s < n - 1:  # the n-th rotate would only ship blocks home
                blk = _rotate(blk)

        used_cnt_present = used_cnt > 0
        used_req_present = contrib > 0

        # ---- tile-local: thresholds at now, reconcile's throttled flags
        thr_cnt, thr_cnt_present, thr_req, thr_req_present = calculate_thresholds(
            sched, now_ns
        )
        st_cnt, st_req, st_req_flag_present = throttled_flags(
            thr_cnt, thr_cnt_present, thr_req, thr_req_present,
            used_cnt, used_cnt_present, used_req, used_req_present,
        )
        state = ThrottleState(
            valid=thr_valid,
            thr_cnt=thr_cnt, thr_cnt_present=thr_cnt_present,
            thr_req=thr_req, thr_req_present=thr_req_present,
            used_cnt=used_cnt, used_cnt_present=used_cnt_present,
            used_req=used_req, used_req_present=used_req_present,
            res_cnt=res_cnt, res_cnt_present=res_cnt_p,
            res_req=res_req, res_req_present=res_req_p,
            st_cnt_throttled=st_cnt, st_req_throttled=st_req,
            st_req_flag_present=st_req_flag_present,
        )

        # ---- sweep 2: rotate blocks with their verdict-count accumulators
        # (sweep 1 left the traveling blocks one hop short of home; start
        # from the locally-held originals instead of shipping them back)
        counts = jnp.zeros((p_loc, 4), dtype=jnp.int32)
        blk2 = (pods, counts)
        for s in range(n):
            origin = (me - s) % n
            start = (origin * p_loc).astype(jnp.int32)
            m = jax.lax.dynamic_slice(mask_cols, (start, jnp.int32(0)), (p_loc, t_loc))
            bpods, bcounts = blk2
            statuses = _classify(state, bpods, m, on_equal, step3_on_equal)  # int8[P/n,T/n]
            bcounts = bcounts + jnp.stack(
                [jnp.sum(statuses == c, axis=1, dtype=jnp.int32) for c in range(4)],
                axis=1,
            )
            blk2 = _rotate((bpods, bcounts))

        _, counts = blk2  # home, complete over all tiles
        schedulable = (
            counts[:, CHECK_ACTIVE]
            + counts[:, CHECK_INSUFFICIENT]
            + counts[:, CHECK_POD_EXCEEDS]
        ) == 0
        return counts, schedulable, used_cnt, used_req, st_cnt, st_req

    from .sharded import uniform_pods_specs, uniform_sched_specs

    ring = P(AXIS)
    sched_specs = uniform_sched_specs(ring)
    pods_specs = uniform_pods_specs(ring)

    mapped = shard_map(
        _sweep,
        mesh=mesh,
        in_specs=(
            sched_specs, pods_specs, P(None, AXIS), ring,
            ring, ring, ring, ring, ring, P(),
        ),
        out_specs=(ring, ring, ring, ring, ring, ring),
    )
    return jax.jit(mapped)


def make_ring_mesh(n_devices: int | None = None) -> Mesh:
    """1D ("ring",) mesh over the first n devices."""
    import numpy as np

    devices = jax.devices()
    n = n_devices or len(devices)
    return Mesh(np.array(devices[:n]), axis_names=(AXIS,))
