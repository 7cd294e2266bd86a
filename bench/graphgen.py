"""Synthetic labeled data graphs at the published sizes of a Table 1 profile.

A copy of the program's generator (``repro.data.graphs.random_labeled_graph``)
kept with the benchmark, so later changes to the program cannot move the
yardstick.  One change: edges are drawn in rounds until the profile's
published number of *distinct* non-self-loop edges is met, and exactly that
many are kept (in first-drawn order).  The program's generator draws the
published count once, and repeated power-law draws and self-loops merge.

Topologies: ``uniform`` (Erdős–Rényi-style), ``powerlaw`` (heavy-tailed
in-degree by a Pareto rank over a random permutation of the nodes).
Labels are Zipf-distributed with ``label_skew``; every label occurs.
"""

from __future__ import annotations

import numpy as np


def _draw(rng: np.random.Generator, n: int, k: int, kind: str,
          perm: np.ndarray):
    src = rng.integers(0, n, size=k)
    if kind == "uniform":
        dst = rng.integers(0, n, size=k)
    elif kind == "powerlaw":
        ranks = (rng.pareto(1.5, size=k) * 3).astype(np.int64) % n
        dst = perm[ranks]
    else:
        raise ValueError(f"unknown topology: {kind}")
    return src, dst


def generate(n: int, n_edges: int, n_labels: int, kind: str,
             label_skew: float, seed: int):
    """Return ``(edges (E, 2) int64, labels (n,) int32)`` with exactly
    ``n_edges`` distinct directed edges and no self-loops."""
    if n_edges > n * (n - 1):
        raise ValueError(f"{n_edges} distinct edges do not fit {n} nodes")
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    keys = np.empty(0, dtype=np.int64)          # src * n + dst, draw order
    while keys.size < n_edges:
        src, dst = _draw(rng, n, max(n_edges - keys.size, 1024) * 2, kind,
                         perm)
        new = (src * n + dst)[src != dst]
        allk = np.concatenate([keys, new])
        _, first = np.unique(allk, return_index=True)
        keys = allk[np.sort(first)]
    keys = keys[:n_edges]
    edges = np.stack([keys // n, keys % n], axis=1)
    w = 1.0 / np.arange(1, n_labels + 1) ** label_skew
    labels = rng.choice(n_labels, size=n, p=w / w.sum()).astype(np.int32)
    # every label of the alphabet is present (|L| is part of the profile):
    # n_labels random nodes carry one label each
    labels[rng.permutation(n)[:n_labels]] = np.arange(n_labels)
    return edges, labels


def from_config(cfg: dict, seed: int):
    """The configuration's graph (its ``nodes``/``edges``/``labels`` keys)."""
    return generate(cfg["nodes"], cfg["edges"], cfg["labels"],
                    cfg["topology"], cfg["label_skew"], seed)


class Csr:
    """Children and parents of every node, from an edge list."""

    def __init__(self, n: int, edges: np.ndarray):
        self.n = n
        self.fwd_ptr, self.fwd = _csr(edges[:, 0], edges[:, 1], n)
        self.bwd_ptr, self.bwd = _csr(edges[:, 1], edges[:, 0], n)

    def children(self, v: int) -> np.ndarray:
        return self.fwd[self.fwd_ptr[v]:self.fwd_ptr[v + 1]]

    def parents(self, v: int) -> np.ndarray:
        return self.bwd[self.bwd_ptr[v]:self.bwd_ptr[v + 1]]


def _csr(src: np.ndarray, dst: np.ndarray, n: int):
    order = np.lexsort((dst, src))
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(ptr, src + 1, 1)
    return np.cumsum(ptr), dst[order].astype(np.int64)
