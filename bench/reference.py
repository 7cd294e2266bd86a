"""Plain reference: the number of occurrences of a hybrid pattern query.

An occurrence is a homomorphism (paper Def. 3.3): every query node maps to
a data node of its label, a child edge ``p/q`` to a data edge, and a
descendant edge ``p//q`` to a directed path of length at least one.  The
served count is exact, and the paper's §7.1 rule stops enumeration after
``cap`` occurrences: a count is correct when it equals the true count, or
equals ``cap`` when the true count is at least ``cap``.

Nothing here comes from the program.  Reachability is a closure over the
strongly connected components (``scipy.sparse.csgraph``), one packed
``uint64`` row per component and direction.  Counting binds the query's
nodes one at a time (most selective first), keeps each partial
assignment's candidates as a packed bitset, and sums the last level's
popcounts.  Partial assignments are expanded depth first in bounded
pieces, so memory stays small and counting can stop at a threshold.

``injective`` turns the counter into the benchmark's control: it counts
only the occurrences that bind distinct data nodes to distinct query
nodes (subgraph isomorphism), which breaks the homomorphism semantics
the configuration guarantees.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components

CHILD = 0
PIECE_ROWS = 256            # partial assignments whose candidates are built at once
EXPAND_ROWS = 1 << 20       # partial assignments materialized at once


class _Stop(Exception):
    pass


def _bit(nodes: np.ndarray) -> np.ndarray:
    return np.left_shift(np.uint64(1), (nodes % 64).astype(np.uint64))


def _closure(n: int, src: np.ndarray, dst: np.ndarray, words: int):
    """Per node its component; per component the packed set of nodes
    reachable from it by a path of length at least one."""
    g = csr_matrix((np.ones(len(src), dtype=np.int8), (src, dst)),
                   shape=(n, n))
    n_comp, comp = connected_components(g, directed=True, connection="strong")
    size = np.bincount(comp, minlength=n_comp)
    cyclic = size > 1
    cyclic[comp[src[src == dst]]] = True
    single = np.zeros(n_comp, dtype=np.int64)       # the node of a singleton
    single[comp] = np.arange(n)
    cs, cd = comp[src], comp[dst]
    keep = cs != cd
    dag = np.unique(np.stack([cs[keep], cd[keep]], axis=1), axis=0)
    succ_ptr = np.searchsorted(dag[:, 0], np.arange(n_comp + 1))
    # Kahn's order of the component DAG; closed in reverse
    indeg = np.bincount(dag[:, 1], minlength=n_comp)
    order: List[int] = list(np.nonzero(indeg == 0)[0])
    head = 0
    while head < len(order):
        c = order[head]
        head += 1
        for d in dag[succ_ptr[c]:succ_ptr[c + 1], 1]:
            indeg[d] -= 1
            if indeg[d] == 0:
                order.append(d)
    closed = np.zeros((n_comp, words), dtype=np.uint64)
    on_cycle = np.nonzero(cyclic[comp])[0]
    np.bitwise_or.at(closed, (comp[on_cycle], on_cycle // 64),
                     _bit(on_cycle))
    for c in reversed(order):
        s = dag[succ_ptr[c]:succ_ptr[c + 1], 1]
        if len(s):
            closed[c] |= np.bitwise_or.reduce(closed[s], axis=0)
            nodes = single[s[~cyclic[s]]]
            np.bitwise_or.at(closed[c], nodes // 64, _bit(nodes))
    return comp, closed


class Reference:
    """Occurrence counts of queries on one labeled graph."""

    def __init__(self, n: int, edges: np.ndarray, labels: np.ndarray):
        self.n = n
        self.words = (n + 63) // 64
        src = edges[:, 0].astype(np.int64)
        dst = edges[:, 1].astype(np.int64)
        self.labels = np.asarray(labels)
        self.children = _adjacency(n, src, dst)
        self.parents = _adjacency(n, dst, src)
        self.fwd = _closure(n, src, dst, self.words)
        self.bwd = _closure(n, dst, src, self.words)
        self._label_bits = {}

    # ---------------------------------------------------------- bitsets
    def label_bits(self, label: int) -> np.ndarray:
        b = self._label_bits.get(label)
        if b is None:
            nodes = np.nonzero(self.labels == label)[0]
            b = np.zeros(self.words, dtype=np.uint64)
            np.bitwise_or.at(b, nodes // 64, np.left_shift(
                np.uint64(1), (nodes % 64).astype(np.uint64)))
            self._label_bits[label] = b
        return b

    def _neighbour_rows(self, adj, nodes: np.ndarray) -> np.ndarray:
        uniq, inv = np.unique(nodes, return_inverse=True)
        row, nb = _neighbours(adj, uniq)
        out = np.zeros((len(uniq), self.words), dtype=np.uint64)
        np.bitwise_or.at(out, (row, nb // 64), _bit(nb))
        return out[inv]

    def _reach_rows(self, closure, nodes: np.ndarray) -> np.ndarray:
        comp, closed = closure
        return closed[comp[nodes]]

    def _image(self, rel, kind: int, bits: np.ndarray) -> np.ndarray:
        """Nodes related by ``rel`` to at least one node of ``bits``."""
        nodes = np.nonzero(np.unpackbits(bits.view(np.uint8),
                                         bitorder="little")[:self.n])[0]
        out = np.zeros(self.words, dtype=np.uint64)
        if kind == CHILD:
            nb = _neighbours(rel, nodes)[1]
            np.bitwise_or.at(out, nb // 64, _bit(nb))
            return out
        comp, closed = rel
        comps = np.unique(comp[nodes])
        for i in range(0, len(comps), 2048):
            out |= np.bitwise_or.reduce(closed[comps[i:i + 2048]], axis=0)
        return out

    def _simulate(self, labels, edges) -> List[np.ndarray]:
        """Candidate sets pruned to the query's double simulation: a
        node stays a candidate of ``s`` only with a related node among
        the candidates of each neighbour (sound for every occurrence)."""
        cand = [self.label_bits(l).copy() for l in labels]
        changed = True
        while changed:
            changed = False
            for s, d, kind in edges:
                fwd, bwd = ((self.children, self.parents) if kind == CHILD
                            else (self.fwd, self.bwd))
                for a, b, rel in ((s, d, bwd), (d, s, fwd)):
                    new = cand[a] & self._image(rel, kind, cand[b])
                    if not np.array_equal(new, cand[a]):
                        cand[a] = new
                        changed = True
        return cand

    # ---------------------------------------------------------- counting
    def _plan(self, labels, edges):
        k = len(labels)
        cand = self._simulate(labels, edges)
        sizes = [int(np.bitwise_count(c).sum()) for c in cand]
        order = [min(range(k), key=lambda v: sizes[v])]
        while len(order) < k:
            rest = [v for v in range(k) if v not in order]
            links = {v: sum(1 for s, d, _ in edges
                            if (s == v and d in order)
                            or (d == v and s in order)) for v in rest}
            order.append(max(rest, key=lambda v: (links[v], -sizes[v])))
        pos = {v: i for i, v in enumerate(order)}
        # per level: (earlier level, relation) constraints on its candidates
        cons = [[] for _ in range(k)]
        for s, d, kind in edges:
            if pos[s] < pos[d]:
                rel = (self.children if kind == CHILD else self.fwd, kind)
                cons[pos[d]].append((pos[s], rel))
            else:
                rel = (self.parents if kind == CHILD else self.bwd, kind)
                cons[pos[s]].append((pos[d], rel))
        return [cand[v] for v in order], cons

    def _candidates(self, base, cons, rows: np.ndarray,
                    injective: bool = False) -> np.ndarray:
        cand = np.broadcast_to(base, (len(rows), self.words)).copy()
        for j, (rel, kind) in cons:
            nodes = rows[:, j]
            cand &= (self._neighbour_rows(rel, nodes) if kind == CHILD
                     else self._reach_rows(rel, nodes))
        if injective:               # no data node bound twice
            at = np.arange(len(rows))
            for j in range(rows.shape[1]):
                cand[at, rows[:, j] // 64] &= ~_bit(rows[:, j])
        return cand

    def _expand(self, rows: np.ndarray, cand: np.ndarray) -> np.ndarray:
        bits = np.unpackbits(cand.view(np.uint8), axis=1,
                             bitorder="little")[:, :self.n]
        r, x = np.nonzero(bits)
        return np.concatenate([rows[r], x[:, None]], axis=1)

    def count(self, labels, edges, stop: Optional[int] = None,
              injective: bool = False) -> int:
        """Occurrences of the query, or a number ``>= stop`` once at least
        ``stop`` are found.  With ``injective``, the control's count (see
        the module docstring)."""
        bases, cons = self._plan(list(labels), list(edges))
        first = np.nonzero(np.unpackbits(bases[0].view(np.uint8),
                                         bitorder="little")[:self.n])[0]
        total = [0]

        def walk(level: int, rows: np.ndarray) -> None:
            for i in range(0, len(rows), PIECE_ROWS):
                piece = rows[i:i + PIECE_ROWS]
                cand = self._candidates(bases[level], cons[level], piece,
                                        injective)
                pc = np.bitwise_count(cand).sum(axis=1, dtype=np.int64)
                if level == len(bases) - 1:
                    total[0] += int(pc.sum())
                    if stop is not None and total[0] >= stop:
                        raise _Stop
                    continue
                # expand in runs of at most EXPAND_ROWS new assignments
                cum = np.cumsum(pc)
                lo = 0
                while lo < len(piece):
                    base = cum[lo - 1] if lo else 0
                    hi = max(lo + 1, int(np.searchsorted(
                        cum, base + EXPAND_ROWS, side="right")))
                    walk(level + 1, self._expand(piece[lo:hi], cand[lo:hi]))
                    lo = hi

        if len(bases) == 1:
            return len(first)
        try:
            walk(1, first[:, None])
        except _Stop:
            pass
        return total[0]


def _neighbours(adj, nodes: np.ndarray):
    """``(position in nodes, neighbour)`` pairs of a CSR adjacency."""
    ptr, idx = adj
    deg = ptr[nodes + 1] - ptr[nodes]
    row = np.repeat(np.arange(len(nodes)), deg)
    within = np.arange(deg.sum()) - np.repeat(np.cumsum(deg) - deg, deg)
    return row, idx[np.repeat(ptr[nodes], deg) + within]


def _adjacency(n: int, src: np.ndarray, dst: np.ndarray):
    order = np.lexsort((dst, src))
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(ptr, src + 1, 1)
    return np.cumsum(ptr), dst[order]
