"""JaxGM — the device-side GM pipeline (single query and vmapped batches).

match(query) = encode → double simulation → JO order (device) → frontier
MJoin.  A batch of queries is the same function under ``vmap`` over the
QueryTensor leaves — the packed graph matrices are one unbatched
argument (shared).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import DataGraph
from ..core.query import PatternQuery
from . import device_graph
from .device_graph import DeviceGraph
from .encoding import QueryTensor, encode_batch, encode_query, jo_order
from .enumerate import MJoinCount, decode_tuples, mjoin_count
from .simulation import double_simulation, fb_sizes, rig_edge_counts


@dataclass
class JaxMatchResult:
    count: int
    overflowed: bool
    fb_sizes: np.ndarray          # |cos(q)| per query node
    tuples: Optional[np.ndarray] = None


def _pipeline(dg: DeviceGraph, qt: QueryTensor, *, n_passes: int,
              exact_sim: bool, capacity: int, impl: str,
              materialize: bool) -> tuple:
    fb = double_simulation(dg, qt, n_passes=n_passes, impl=impl,
                           exact=exact_sim)
    sizes = fb_sizes(fb)
    order = jo_order(qt, sizes)
    res = mjoin_count(dg, qt, fb, order, capacity=capacity,
                      materialize=materialize)
    return res, sizes, order


class JaxGM:
    """Device matcher bound to one data graph.

    The pipeline is compiled **ahead of time** once per shape — single
    query with or without materialization, or a vmapped batch of a given
    size — with the wall time kept in ``compile_s`` apart from
    ``kernel_s``, the fenced device time of the dispatches.
    :meth:`prepare` / :meth:`prepare_batch` compile and return the
    dispatch as a zero-argument callable, so callers can govern the
    dispatch alone (a compile error raises from ``prepare*``, never from
    inside a retried dispatch).
    """

    def __init__(self, graph: DataGraph, *, max_q: int = 8, max_e: int = 16,
                 block: int = 512, capacity: int = 4096, n_passes: int = 4,
                 exact_sim: bool = False, impl: str = "auto",
                 closure_on_device: bool = False,
                 use_transitive_reduction: bool = True):
        self.graph = graph
        self.max_q, self.max_e = max_q, max_e
        self.capacity, self.n_passes = capacity, n_passes
        self.exact_sim, self.impl = exact_sim, impl
        self.use_tr = use_transitive_reduction
        self.dg = device_graph.from_host(graph, block=block,
                                         closure_on_device=closure_on_device,
                                         impl=impl)
        self.calls = 0            # device dispatches
        self.compile_s = 0.0      # one-time AOT compile time per shape
        self.kernel_s = 0.0       # fenced per-dispatch device time
        self._compiled = {}

    def _prep(self, q: PatternQuery) -> tuple:
        if self.use_tr:
            q = q.transitive_reduction()
        return q, encode_query(q, self.max_q, self.max_e)

    def _executor(self, qt: QueryTensor, batch: Optional[int],
                  materialize: bool):
        key = (batch, materialize)
        fn = self._compiled.get(key)
        if fn is None:
            single = partial(_pipeline, n_passes=self.n_passes,
                             exact_sim=self.exact_sim,
                             capacity=self.capacity, impl=self.impl,
                             materialize=materialize)
            f = single if batch is None else jax.vmap(single,
                                                      in_axes=(None, 0))
            t0 = time.perf_counter()
            fn = jax.jit(f).lower(self.dg, qt).compile()
            self.compile_s += time.perf_counter() - t0
            self._compiled[key] = fn
        return fn

    def _dispatch(self, fn, qt: QueryTensor):
        t0 = time.perf_counter()
        out = fn(self.dg, qt)
        jax.block_until_ready(out)
        self.kernel_s += time.perf_counter() - t0
        self.calls += 1
        return out

    def prepare(self, q: PatternQuery, materialize: bool = False
                ) -> Callable[[], JaxMatchResult]:
        q, qt = self._prep(q)
        fn = self._executor(qt, None, materialize)

        def run() -> JaxMatchResult:
            res, sizes, order = self._dispatch(fn, qt)
            tuples = None
            if materialize:
                tuples = decode_tuples(res, order, q.n)
            return JaxMatchResult(count=int(res.count),
                                  overflowed=bool(res.overflowed),
                                  fb_sizes=np.asarray(sizes)[:q.n],
                                  tuples=tuples)
        return run

    def match(self, q: PatternQuery,
              materialize: bool = False) -> JaxMatchResult:
        return self.prepare(q, materialize)()

    def prepare_batch(self, queries: Sequence[PatternQuery]
                      ) -> Callable[[], List[JaxMatchResult]]:
        prepped = [self._prep(q) for q in queries]
        qts = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[qt for _, qt in prepped])
        fn = self._executor(qts, len(prepped), False)

        def run() -> List[JaxMatchResult]:
            res, sizes, _ = self._dispatch(fn, qts)
            count, over = np.asarray(res.count), np.asarray(res.overflowed)
            sizes = np.asarray(sizes)
            return [JaxMatchResult(count=int(count[i]),
                                   overflowed=bool(over[i]),
                                   fb_sizes=sizes[i][:q.n])
                    for i, (q, _) in enumerate(prepped)]
        return run

    def match_batch(self, queries: Sequence[PatternQuery]
                    ) -> List[JaxMatchResult]:
        return self.prepare_batch(queries)()

    def rig_stats(self, q: PatternQuery):
        """(fb sizes, per-edge RIG edge counts) — Fig. 9 statistics."""
        q, qt = self._prep(q)
        fb = double_simulation(self.dg, qt, n_passes=self.n_passes,
                               impl=self.impl, exact=self.exact_sim)
        return (np.asarray(fb_sizes(fb))[:q.n],
                np.asarray(rig_edge_counts(self.dg, qt, fb, impl=self.impl))[:q.m])
