"""Distributed GM: SUMMA-style sharded double simulation + serving step.

Layout over the production mesh (``("data","model")`` per pod, plus a
leading ``"pod"`` axis across pods):

* packed matrices (A, R, Aᵀ, Rᵀ): rows sharded over ``("pod","data")``,
  packed word-columns sharded over ``"model"`` — 2-D block layout; a 2²⁰-node
  graph is 128 GB packed ⇒ 256 MB/chip on 512 chips.
* FB candidate matrix: node dimension sharded over ``"model"`` (aligned with
  the matrices' column blocks), replicated over ``("pod","data")``.
* one simulation pass =
    local blocked ``bitmm`` on the (row-block × word-block) tile
    → ``psum`` over ``model``  (contraction over node columns)
    → ``all_gather`` over ``("pod","data")`` (rebuild full Y)
    → slice this shard's node range, apply edge masks locally.

The enumeration phase deliberately stays *pod-local*: after double
simulation the RIG is tiny (paper Fig. 9: ≈0.4% of the data graph), so
candidates are compacted (top-K per query node) and handed to the
single-pod frontier enumerator — the distributed phase is the filter, as
in the paper's architecture.  ``gm_serve_step`` is the unit the multi-pod
dry-run lowers and the roofline analyses.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import packed
from ..kernels.ops import _bitmm_blocked
from .device_graph import DeviceGraph
from .encoding import QueryTensor

ROW_AXES = ("pod", "data")     # matrix rows (only axes present in the mesh)
COL_AXIS = "model"             # packed word columns / FB node dim


def _axes(mesh: Mesh):
    row_axes = tuple(a for a in ROW_AXES if a in mesh.axis_names)
    assert COL_AXIS in mesh.axis_names
    return row_axes, COL_AXIS


class ShardedGraphSpecs(NamedTuple):
    """ShapeDtypeStructs + shardings for the packed graph (dry-run inputs)."""
    mats: jax.ShapeDtypeStruct         # (4, Np, Np/32) uint32
    labels: jax.ShapeDtypeStruct       # (Np,) int32
    mats_sharding: NamedSharding
    labels_sharding: NamedSharding


def graph_specs(n_pad: int, mesh: Mesh) -> ShardedGraphSpecs:
    row_axes, col = _axes(mesh)
    w = n_pad // 32
    return ShardedGraphSpecs(
        mats=jax.ShapeDtypeStruct((4, n_pad, w), jnp.uint32),
        labels=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        mats_sharding=NamedSharding(mesh, P(None, row_axes, col)),
        labels_sharding=NamedSharding(mesh, P(col)),
    )


# --------------------------------------------------------------- sim pass
def _local_pass(mats_blk, fb_blk, qt: QueryTensor, *, row_axes, col_axis,
                n_pad: int, block_k: int, unroll: bool = False,
                pack_y: bool = False):
    """shard_map body for one Jacobi double-simulation pass over a BATCH of
    queries.  mats_blk: (4, rows_l, w_l) uint32; fb_blk: (B, max_q, np_l)
    bool.  Returns the pruned fb_blk.

    ``pack_y`` (§Perf H4): the all-gathered Y is pure bits; packing to
    uint32 before the gather cuts its wire bytes 8× (bool is 1 byte on the
    wire) at the cost of one pack/unpack pair of VPU ops per pass.
    """
    b, max_q, np_l = fb_blk.shape
    rows_l = mats_blk.shape[1]

    # contraction operand: all queries' FB side by side -> one matmul/matrix
    x = fb_blk.transpose(2, 0, 1).reshape(np_l, b * max_q).astype(jnp.float32)
    ys = []
    for m in range(4):                                   # A, R, At, Rt
        part = _bitmm_blocked(mats_blk[m], x, threshold=False,
                              block_k=min(block_k, np_l), unroll=unroll)
        ys.append(part)
    y = jnp.stack(ys)                                    # (4, rows_l, B*max_q)
    y = jax.lax.psum(y, col_axis)                        # contract node cols
    y = y > 0
    if pack_y:
        yw = packed.pack(y)                              # (4, rows_l, BQ/32)
        for ax in reversed(row_axes):
            yw = jax.lax.all_gather(yw, ax, axis=1, tiled=True)
        y = packed.unpack(yw, b * max_q)                 # (4, Np, B*max_q)
    else:
        for ax in reversed(row_axes):                    # rebuild full rows
            y = jax.lax.all_gather(y, ax, axis=1, tiled=True)
    col_id = jax.lax.axis_index(col_axis)
    y_mine = jax.lax.dynamic_slice_in_dim(y, col_id * np_l, np_l, axis=1)
    y_mine = y_mine.reshape(4, np_l, b, max_q).transpose(0, 2, 3, 1)
    # (4, B, max_q, np_l): [fwd-child, fwd-desc, bwd-child, bwd-desc]

    def apply_masks(fb_q, y_q, qt_q):
        keep = jnp.ones_like(fb_q)
        for e in range(qt_q.max_e):
            src = qt_q.edge_src[e]
            dst = qt_q.edge_dst[e]
            kind = qt_q.edge_kind[e]
            valid = kind >= 0
            k = jnp.clip(kind, 0, 1)
            m_f = jnp.take(y_q[k], dst, axis=0)          # (np_l,)
            m_b = jnp.take(y_q[2 + k], src, axis=0)
            oh_s = jax.nn.one_hot(src, qt_q.max_q, dtype=bool)
            oh_d = jax.nn.one_hot(dst, qt_q.max_q, dtype=bool)
            keep &= ~oh_s[:, None] | m_f[None, :] | ~valid
            keep &= ~oh_d[:, None] | m_b[None, :] | ~valid
        return fb_q & keep

    y_by_query = y_mine.transpose(1, 0, 2, 3)            # (B, 4, max_q, np_l)
    return jax.vmap(apply_masks)(fb_blk, y_by_query, qt)


def sharded_double_simulation(mats: jax.Array, labels: jax.Array,
                              qts: QueryTensor, mesh: Mesh, *,
                              n_passes: int = 4, block_k: int = 4096,
                              unroll: bool = False,
                              pack_y: bool = False) -> jax.Array:
    """FB for a batch of queries: (B, max_q, n_pad) bool, node dim sharded
    over the ``model`` axis.  ``qts`` leaves carry a leading batch dim."""
    row_axes, col = _axes(mesh)
    n_pad = mats.shape[1]

    fb0 = (qts.labels[:, :, None] == labels[None, None, :]) & \
        (qts.labels[:, :, None] >= 0)                      # (B, max_q, Np)

    body = functools.partial(_local_pass, row_axes=row_axes, col_axis=col,
                             n_pad=n_pad, block_k=block_k, unroll=unroll,
                             pack_y=pack_y)
    qt_specs = jax.tree.map(lambda _: P(), qts)

    pass_sharded = jax.shard_map(
        lambda m, f, q: body(m, f, q),
        mesh=mesh,
        in_specs=(P(None, row_axes, col), P(None, None, col), qt_specs),
        out_specs=P(None, None, col),
        check_vma=False,
    )
    fb = fb0
    for _ in range(n_passes):
        fb = pass_sharded(mats, fb, qts)
    return fb


# -------------------------------------------------------------- serve step
class ServeStepOut(NamedTuple):
    fb_sizes: jax.Array        # (B, max_q) int32   |cos(q)|
    edge_counts: jax.Array     # (B, max_e) float32 RIG edge cardinalities
    candidates: jax.Array      # (B, max_q, top_k) int32 compacted RIG handoff


def gm_serve_step(mats: jax.Array, labels: jax.Array, qts: QueryTensor,
                  mesh: Mesh, *, n_passes: int = 4, top_k: int = 4096,
                  block_k: int = 4096, unroll: bool = False,
                  pack_y: bool = False) -> ServeStepOut:
    """The distributed query-serving step (dry-run unit).

    double simulation (n_passes) → RIG statistics → candidate compaction
    (top-K node ids per query node, the pod-local enumeration handoff).
    """
    fb = sharded_double_simulation(mats, labels, qts, mesh,
                                   n_passes=n_passes, block_k=block_k,
                                   unroll=unroll, pack_y=pack_y)
    sizes = fb.sum(axis=2).astype(jnp.int32)               # (B, max_q)

    # RIG edge counts: one more sum-semantics pass over fwd matrices
    row_axes, col = _axes(mesh)
    n_pad = mats.shape[1]
    b, max_q, _ = fb.shape

    def count_body(mats_blk, fb_blk, qts_):
        bq = fb_blk.shape[0] * fb_blk.shape[1]
        np_l = fb_blk.shape[2]
        x = fb_blk.transpose(2, 0, 1).reshape(np_l, bq).astype(jnp.float32)
        cnt = jnp.stack([
            _bitmm_blocked(mats_blk[0], x, threshold=False,
                           block_k=min(block_k, np_l), unroll=unroll),
            _bitmm_blocked(mats_blk[1], x, threshold=False,
                           block_k=min(block_k, np_l), unroll=unroll),
        ])                                               # (2, rows_l, B*max_q)
        cnt = jax.lax.psum(cnt, col)
        for ax in reversed(row_axes):
            cnt = jax.lax.all_gather(cnt, ax, axis=1, tiled=True)
        col_id = jax.lax.axis_index(col)
        mine = jax.lax.dynamic_slice_in_dim(cnt, col_id * np_l, np_l, axis=1)
        mine = mine.reshape(2, np_l, fb_blk.shape[0], max_q)
        mine = mine.transpose(2, 0, 3, 1)                # (B, 2, max_q, np_l)

        def per_query(fb_q, cnt_q, qt_q):
            out = []
            for e in range(qt_q.max_e):
                src, dst, kind = (qt_q.edge_src[e], qt_q.edge_dst[e],
                                  qt_q.edge_kind[e])
                valid = kind >= 0
                per_node = jnp.take(cnt_q[jnp.clip(kind, 0, 1)], dst, axis=0)
                masked = jnp.where(fb_q[src], per_node, 0.0)
                out.append(jnp.where(valid, masked.sum(), 0.0))
            return jnp.stack(out)

        partial_counts = jax.vmap(per_query)(fb_blk, mine, qts_)
        return jax.lax.psum(partial_counts, col)         # sum node shards

    qt_specs = jax.tree.map(lambda _: P(), qts)
    edge_counts = jax.shard_map(
        count_body, mesh=mesh,
        in_specs=(P(None, row_axes, col), P(None, None, col), qt_specs),
        out_specs=P(),
        check_vma=False,
    )(mats, fb, qts)

    # candidate compaction (§Perf H6): a *global* top_k over the sharded
    # 1M-node axis makes XLA all-gather + sort the whole (B, max_q, N)
    # score tensor (tens of GB of temp).  Exact alternative: every member
    # of the global top-K is in its own shard's local top-K, so take a
    # local top-K per model shard inside shard_map, all-gather the (small)
    # (n_shards · K) id/flag lists, and merge with one tiny top_k.
    def compact_body(fb_blk):
        np_l = fb_blk.shape[2]
        col_id = jax.lax.axis_index(col)
        scores = fb_blk.astype(jnp.int32) * (np_l + 1) - \
            jnp.arange(np_l, dtype=jnp.int32)[None, None, :] % (np_l + 1)
        s_loc, idx_loc = jax.lax.top_k(scores, min(top_k, np_l))
        gid = idx_loc + col_id * np_l
        flag = jnp.take_along_axis(fb_blk, idx_loc, axis=2)
        gid = jnp.where(flag, gid, -1)
        # gather all shards' lists (small: n_shards × K ints per (b, q))
        gid_all = jax.lax.all_gather(gid, col, axis=2, tiled=True)
        flag_all = jax.lax.all_gather(flag, col, axis=2, tiled=True)
        merged_scores = jnp.where(flag_all, n_pad - gid_all, -1)
        _, take = jax.lax.top_k(merged_scores, top_k)
        out = jnp.take_along_axis(gid_all, take, axis=2)
        return out.astype(jnp.int32)

    candidates = jax.shard_map(
        compact_body, mesh=mesh,
        in_specs=(P(None, None, col),),
        out_specs=P(),                      # replicated (it is small)
        check_vma=False,
    )(fb)
    return ServeStepOut(fb_sizes=sizes, edge_counts=edge_counts,
                        candidates=candidates)


# ------------------------------------------------------------ host helpers
def shard_graph_arrays(dg: DeviceGraph, mesh: Mesh):
    """Place a real DeviceGraph onto the mesh (multi-device CPU tests)."""
    specs = graph_specs(dg.n_pad, mesh)
    mats = jnp.stack([dg.adj, dg.reach, dg.adj_t, dg.reach_t])
    mats = jax.device_put(mats, specs.mats_sharding)
    labels = jax.device_put(dg.labels, specs.labels_sharding)
    return mats, labels
