"""Query traffic: connected-subgraph queries (paper §7.1) drawn per mix.

``random_query`` is a copy of the program's
``repro.data.queries.random_query_from_graph``, kept with the benchmark so
later changes to the program cannot move the yardstick.  A query is a
``Query`` of node labels and ``(src, dst, kind)`` edges, ``kind`` 0 for a
child edge ``/`` and 1 for a descendant edge ``//``.

``Traffic`` draws a mix's requests: request ``j`` of the run takes the
``j``-th entry of the mix's class-and-size cycle, so every window holds
the same mix; the instance comes from ``(seed, stream, j)``.  Requests are
distinct: no two of a run (warm-up included) share their multiset of node
labels, so no two can share a canonical form, a plan-cache entry or a
batch slot by deduplication.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from graphgen import Csr

CHILD, DESC = 0, 1
WINDOW, WARMUP = 0, 1          # instance streams


@dataclass(frozen=True)
class Query:
    labels: Tuple[int, ...]
    edges: Tuple[Tuple[int, int, int], ...]     # (src, dst, kind)
    qclass: str
    name: str

    @property
    def n(self) -> int:
        return len(self.labels)


def _kinds(edges, qtype: str, rng: np.random.Generator):
    out = []
    for s, d in edges:
        if qtype == "C":
            k = CHILD
        elif qtype == "D":
            k = DESC
        elif qtype == "H":
            k = DESC if rng.random() < 0.5 else CHILD
        else:
            raise ValueError(f"unknown query class {qtype}")
        out.append((s, d, k))
    return out


def random_query(g: Csr, labels: np.ndarray, n_nodes: int, qtype: str,
                 seed: int, extra_edge_prob: float = 0.3) -> Query:
    """A connected subgraph of the data graph with ``n_nodes`` nodes
    (at least one occurrence before edge kinds are assigned; descendant
    edges only widen the answer)."""
    rng = np.random.default_rng(seed)
    for _attempt in range(64):
        start = int(rng.integers(0, g.n))
        nodes = [start]
        seen = {start}
        frontier = [start]
        while len(nodes) < n_nodes and frontier:
            v = frontier.pop(int(rng.integers(0, len(frontier))))
            nbrs = np.concatenate([g.children(v), g.parents(v)])
            rng.shuffle(nbrs)
            for w in nbrs:
                w = int(w)
                if w not in seen:
                    seen.add(w)
                    nodes.append(w)
                    frontier.append(w)
                    if len(nodes) >= n_nodes:
                        break
        if len(nodes) >= n_nodes:
            break
    nodes = nodes[:n_nodes]
    pos = {v: i for i, v in enumerate(nodes)}
    node_set = set(nodes)
    edges = []
    for v in nodes:
        for w in g.children(v):
            if int(w) in node_set:
                edges.append((pos[v], pos[int(w)]))
    edges = sorted(set(edges))
    if not edges:
        return random_query(g, labels, n_nodes, qtype, seed + 1,
                            extra_edge_prob)
    keep = []
    connected = {edges[0][0]}
    progress = True
    while progress:
        progress = False
        for e in edges:
            if e in keep:
                continue
            if e[0] in connected or e[1] in connected:
                keep.append(e)
                connected |= {e[0], e[1]}
                progress = True
    for e in edges:
        if e not in keep and rng.random() < extra_edge_prob:
            keep.append(e)
    used = sorted({x for e in keep for x in e})
    remap = {v: i for i, v in enumerate(used)}
    keep = [(remap[a], remap[b]) for a, b in keep]
    return Query(labels=tuple(int(labels[nodes[v]]) for v in used),
                 edges=tuple(_kinds(keep, qtype, rng)), qclass=qtype,
                 name=f"{qtype}{n_nodes}_s{seed}")


class Traffic:
    """The requests of one run of a mix on one graph."""

    def __init__(self, mix: dict, g: Csr, labels: np.ndarray, seed: int):
        self.cycle = [(c, n) for n in mix["nodes"] for c in mix["classes"]]
        self.max_nodes = mix["max_nodes"]
        self.max_edges = mix["max_edges"]
        self.g, self.labels, self.seed = g, labels, seed
        self._seen = set()
        self._drawn = {}

    def request(self, j: int, stream: int = WINDOW) -> Query:
        """Request ``j`` of ``stream``, drawn on first use.  Draw in order
        of ``j`` within each stream, so the distinctness rule is the same
        on every run of the seed."""
        q = self._drawn.get((stream, j))
        if q is None:
            q = self._drawn[(stream, j)] = self._draw(j, stream)
        return q

    def _draw(self, j: int, stream: int) -> Query:
        qclass, n = self.cycle[j % len(self.cycle)]
        for attempt in range(10_000):
            inst = int(np.random.SeedSequence(
                [self.seed, stream, j, attempt]).generate_state(1)[0])
            q = random_query(self.g, self.labels, n, qclass, inst)
            sig = tuple(sorted(q.labels))
            if (q.n <= self.max_nodes and len(q.edges) <= self.max_edges
                    and sig not in self._seen):
                self._seen.add(sig)
                return q
        raise RuntimeError(f"no distinct {qclass}{n} query after 10000 draws")

    def requests(self, count: int, stream: int = WINDOW) -> List[Query]:
        return [self.request(j, stream) for j in range(count)]
