"""Frontier-vectorized MJoin (TPU adaptation of Alg. 5).

``jax.lax`` control flow cannot express unbounded recursion, so the
backtracking enumeration becomes a *level-synchronous frontier expansion*:
a fixed-capacity table of partial assignments is extended one query node at
a time (following the search order), where each extension is the same
multiway packed-bitset intersection as the paper's — ``cos(q_i)`` AND one
RIG adjacency row per bound neighbour — realized as flat gathers over the
stacked packed matrices plus word-wise ANDs (the ``intersect`` kernel's
semantics).  Intermediate results remain intersections (never joins), so
the "no exploding intermediates" property carries over; a capacity overflow
is *detected and reported* rather than silently truncated.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import packed
from .device_graph import DeviceGraph
from .encoding import PAD, QueryTensor


class MJoinCount(NamedTuple):
    count: jax.Array          # int32 — exact iff not overflowed
    overflowed: jax.Array     # bool
    frontier: jax.Array       # (capacity, max_q) int32 — last-level partials
    alive: jax.Array          # (capacity,) bool


def _inverse_order(order: jax.Array, max_q: int) -> jax.Array:
    # PAD entries clip onto index 0 — use a min-scatter so duplicate writes
    # from padding cannot clobber a real node's position.
    inv = jnp.full(max_q, max_q + 1, jnp.int32)     # unreachable position
    pos = jnp.arange(max_q, dtype=jnp.int32)
    safe = jnp.clip(order, 0, max_q - 1)
    updates = jnp.where(order >= 0, pos, max_q + 1)
    return inv.at[safe].min(updates)


@partial(jax.jit, static_argnames=("capacity", "materialize"))
def mjoin_count(dg: DeviceGraph, qt: QueryTensor, fb: jax.Array,
                order: jax.Array, *, capacity: int = 4096,
                materialize: bool = False) -> MJoinCount:
    """Count (and optionally materialize up to ``capacity``) occurrences.

    fb: (max_q, n_pad) bool — the double-simulation candidate sets;
    order: (max_q,) int32 search order (PAD beyond n_nodes).
    """
    np_, max_q, max_e = dg.n_pad, qt.max_q, qt.max_e
    w = dg.n_words
    # operand by matrix id = 2 * is_backward + (kind == DESC); gathered
    # per matrix and selected, so no stacked copy of the graph is made
    mats = (dg.adj, dg.reach, dg.adj_t, dg.reach_t)
    fb_words = packed.pack(fb)                       # (max_q, W)
    inv = _inverse_order(order, max_q)

    assign = jnp.full((capacity, max_q), PAD, jnp.int32)
    alive = jnp.zeros(capacity, bool).at[0].set(True)
    total = jnp.int32(0)
    overflow = jnp.bool_(False)

    for i in range(max_q):                           # static levels
        qi = jnp.clip(order[i], 0, max_q - 1)
        active = i < qt.n_nodes
        is_last = i == qt.n_nodes - 1

        def constrain(e, cand):
            """AND in edge e's adjacency row when it binds level i."""
            src, dst, kind = qt.edge_src[e], qt.edge_dst[e], qt.edge_kind[e]
            valid = kind >= 0
            psrc = jnp.take(inv, jnp.clip(src, 0, max_q - 1))
            pdst = jnp.take(inv, jnp.clip(dst, 0, max_q - 1))
            f_app = valid & (pdst == i) & (psrc < i)   # src bound -> fwd row
            b_app = valid & (psrc == i) & (pdst < i)   # dst bound -> bwd row
            jpos = jnp.where(f_app, psrc, pdst)
            mat_id = jnp.where(f_app, 0, 2) + jnp.clip(kind, 0, 1)
            t_col = jnp.take(assign, jnp.clip(jpos, 0, max_q - 1), axis=1)
            row_idx = jnp.clip(t_col, 0, np_ - 1)
            rows = jnp.take(mats[0], row_idx, axis=0)            # (F, W)
            for m in range(1, 4):
                rows = jnp.where(mat_id == m,
                                 jnp.take(mats[m], row_idx, axis=0), rows)
            return jnp.where(f_app | b_app, cand & rows, cand)

        # a rolled loop: one edge's gathers live at a time
        cand = jax.lax.fori_loop(
            0, max_e, constrain,
            jnp.broadcast_to(jnp.take(fb_words, qi, axis=0)[None, :],
                             (capacity, w)))

        cand = jnp.where(alive[:, None], cand, jnp.uint32(0))
        counts = packed.popcount(cand).sum(axis=1)               # (F,)
        level_total = counts.sum()
        total = total + jnp.where(active & is_last, level_total, 0)

        # --- expand (all non-last active levels; last too if materializing)
        # the first `capacity` set bits in row-major order, selected from
        # the packed words (no dense (F, Np) unpack or sort)
        parent, node, valid_new = packed.first_set_bits(cand, capacity)
        new_assign = jnp.take(assign, parent, axis=0).at[:, i].set(
            jnp.where(valid_new, node, PAD))
        do_expand = active & (~is_last | jnp.bool_(materialize))
        overflow = overflow | (active & ~is_last & (level_total > capacity))
        assign = jnp.where(do_expand, new_assign, assign)
        alive = jnp.where(do_expand, valid_new, alive)

    return MJoinCount(count=total, overflowed=overflow,
                      frontier=assign, alive=alive)


def decode_tuples(res: MJoinCount, order, n_nodes: int):
    """Host-side: frontier rows -> occurrence tuples in query-node order."""
    import numpy as np
    assign = np.asarray(res.frontier)[np.asarray(res.alive)]
    order = np.asarray(order)[:n_nodes]
    out = np.full((assign.shape[0], n_nodes), -1, dtype=np.int64)
    for pos, qnode in enumerate(order):
        out[:, int(qnode)] = assign[:, pos]
    return out
