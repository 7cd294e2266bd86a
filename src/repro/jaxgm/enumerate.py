"""Frontier-vectorized MJoin (TPU adaptation of Alg. 5).

``jax.lax`` control flow cannot express unbounded recursion, so the
backtracking enumeration becomes a *level-synchronous frontier expansion*:
a fixed-capacity table of partial assignments is extended one query node at
a time (following the search order), where each extension is the same
multiway packed-bitset intersection as the paper's — ``cos(q_i)`` AND one
RIG adjacency row per bound neighbour — realized as row gathers from the
four packed matrices plus word-wise ANDs (the ``intersect`` kernel's
semantics).  Intermediate results remain intersections (never joins), so
the "no exploding intermediates" property carries over; a capacity overflow
is *detected and reported* rather than silently truncated.

An edge binds exactly one level, the later of its endpoints' positions in
the search order, so the edges are sorted by that level once and each
level's constraint loop runs over its own segment only: a query costs one
row gather per edge in all, not ``max_q × max_e`` (a vmapped batch, per
level the most of any of its queries).
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
    level_edges: jax.Array    # (max_q,) int32 — edges that bind each level


def _inverse_order(order: jax.Array, max_q: int) -> jax.Array:
    # PAD entries clip onto index 0 — use a min-scatter so duplicate writes
    # from padding cannot clobber a real node's position.
    inv = jnp.full(max_q, max_q + 1, jnp.int32)     # unreachable position
    pos = jnp.arange(max_q, dtype=jnp.int32)
    safe = jnp.clip(order, 0, max_q - 1)
    updates = jnp.where(order >= 0, pos, max_q + 1)
    return inv.at[safe].min(updates)


def _edges_by_level(qt: QueryTensor, inv: jax.Array):
    """The query edges sorted (stably) by the level they bind.

    Edge ``e`` binds level ``max(inv[src], inv[dst])``: the later of its
    endpoints fixes the new node against the earlier, bound one.  Padding
    edges and edges whose endpoints share a position bind no level.
    Returns ``(start, k, jpos, mat_id)``: level ``i``'s edges are
    ``[start[i], start[i] + k[i])`` of the sorted ``jpos`` (the bound
    endpoint's position) and ``mat_id`` (operand matrix: ``2 *
    is_backward + (kind == DESC)``).
    """
    max_q = qt.max_q
    kind = qt.edge_kind
    psrc = jnp.take(inv, jnp.clip(qt.edge_src, 0, max_q - 1))
    pdst = jnp.take(inv, jnp.clip(qt.edge_dst, 0, max_q - 1))
    binds = (kind >= 0) & (psrc != pdst)
    level = jnp.where(binds, jnp.maximum(psrc, pdst), max_q)
    perm = jnp.argsort(level, stable=True)
    k = (level[None, :] == jnp.arange(max_q)[:, None]).sum(
        axis=1, dtype=jnp.int32)
    start = jnp.cumsum(k) - k
    jpos = jnp.clip(jnp.minimum(psrc, pdst), 0, max_q - 1)
    # src bound first -> its forward row; dst bound first -> backward row
    mat_id = jnp.where(psrc < pdst, 0, 2) + jnp.clip(kind, 0, 1)
    return start, k, jnp.take(jpos, perm), jnp.take(mat_id, perm)


@partial(jax.jit, static_argnames=("capacity", "materialize"))
def mjoin_count(dg: DeviceGraph, qt: QueryTensor, fb: jax.Array,
                order: jax.Array, *, capacity: int = 4096,
                materialize: bool = False) -> MJoinCount:
    """Count (and optionally materialize up to ``capacity``) occurrences.

    fb: (max_q, n_pad) bool — the double-simulation candidate sets;
    order: (max_q,) int32 search order (PAD beyond n_nodes).

    Level ``i`` ANDs ``cos(order[i])`` with one adjacency row per edge
    that binds it, looping over those edges only (``level_edges[i]``
    trips; under ``vmap`` the batch's largest).
    """
    np_, max_q, max_e = dg.n_pad, qt.max_q, qt.max_e
    w = dg.n_words
    # operand by matrix id = 2 * is_backward + (kind == DESC); gathered
    # per matrix and selected, so no stacked copy of the graph is made
    mats = (dg.adj, dg.reach, dg.adj_t, dg.reach_t)
    fb_words = packed.pack(fb)                       # (max_q, W)
    inv = _inverse_order(order, max_q)
    start, k, jpos, mat_id = _edges_by_level(qt, inv)

    assign = jnp.full((capacity, max_q), PAD, jnp.int32)
    alive = jnp.zeros(capacity, bool).at[0].set(True)
    total = jnp.int32(0)
    overflow = jnp.bool_(False)

    for i in range(max_q):                           # static levels
        qi = jnp.clip(order[i], 0, max_q - 1)
        active = i < qt.n_nodes
        is_last = i == qt.n_nodes - 1

        def constrain(e, cand):
            """AND in sorted edge e's row of its bound endpoint."""
            e = jnp.minimum(e, max_e - 1)    # a finished vmap lane's index
            t_col = jnp.take(assign, jpos[e], axis=1)
            row_idx = jnp.clip(t_col, 0, np_ - 1)
            rows = jnp.take(mats[0], row_idx, axis=0)            # (F, W)
            for m in range(1, 4):
                rows = jnp.where(mat_id[e] == m,
                                 jnp.take(mats[m], row_idx, axis=0), rows)
            return cand & rows

        # a rolled loop over the level's own edges: one edge's gathers
        # live at a time
        cand = jax.lax.fori_loop(
            start[i], start[i] + k[i], constrain,
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
                      frontier=assign, alive=alive, level_edges=k)


def decode_tuples(res: MJoinCount, order, n_nodes: int):
    """Host-side: frontier rows -> occurrence tuples in query-node order."""
    import numpy as np
    assign = np.asarray(res.frontier)[np.asarray(res.alive)]
    order = np.asarray(order)[:n_nodes]
    out = np.full((assign.shape[0], n_nodes), -1, dtype=np.int64)
    for pos, qnode in enumerate(order):
        out[:, int(qnode)] = assign[:, pos]
    return out
