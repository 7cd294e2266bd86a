#!/usr/bin/env python3
"""Chip smoke: the engine's two device paths on one TPU, at the full
Epinions profile of the paper's Table 1 (75,879 nodes, 20 labels,
power-law topology; seed 0).  The generator draws the profile's 508,837
edges; 324,130 distinct edges remain once repeated draws and self-loops
are merged.

Run from the checkout root, on a machine with a TPU::

    python3 chip_smoke.py

One process, no children.  Phases:

0. **Backend.**  JAX must report a TPU; otherwise the script exits
   non-zero before any query.  Prints the device kind and count, the
   resolved frontier mode (must be ``pallas``) and the kernel impl.
1. **Served path.**  ``QueryServer(graph)`` with its defaults —
   ``execute_many`` and the vmapped device matcher (bitmm double
   simulation + frontier MJoin on device).  Every request must end
   ``done`` with the host ``GM`` count, at least ``MIN_DEVICE_EXACT`` of
   them answered on the device without capacity overflow, with no
   degradation, no device retry and a closed breaker.
2. **Resident enumerator.**  ``Engine(graph, force_backend="host")``: the
   planner's own enum pick for dense queries is
   ``frontier-device-resident`` (``gather_intersect`` + ``expand_pairs``
   against the device-resident RIG).  Queries run through ``execute``
   and one through ``execute_stream``; counts must equal host ``GM``,
   each query must dispatch on the device, with no degradation.

Every line before the last is a report (per query: backend, enum
method, count, overflow, wall ms; executor compile time apart from
kernel time; graph size; device peak bytes).  The last line is exactly
``{"ok": true, "device": {...}}`` and is printed only when every check
passed.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRAPH = ("epinions", 1.0, 0)           # paper profile, scale, seed
# (qtype, query nodes, seed) for data.queries.random_query_from_graph.
# Served: C/H/D, 3-5 nodes, one padded shape (max_q=8, max_e=16); two of
# them (C 3/0, D 3/0) have a partial level over the device capacity and
# exercise the exact host fallback.  Resident: dense D queries, whose
# estimated answer sets make the planner pick the resident enumerator and
# whose frontiers are wide enough (>= 128 rows) to be dispatched.
SERVED = [("C", 3, 3), ("C", 4, 0), ("C", 4, 3), ("H", 4, 0), ("H", 4, 3),
          ("H", 5, 0), ("D", 3, 1), ("D", 3, 3), ("D", 4, 1), ("D", 4, 3),
          ("C", 3, 0), ("D", 3, 0)]
RESIDENT = [("D", 4, 0), ("D", 4, 1)]
STREAMED = ("D", 3, 2)
MIN_DEVICE_EXACT = 4


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def make_queries(graph, specs):
    from repro.data.queries import random_query_from_graph
    return [random_query_from_graph(graph, n, qtype=t, seed=s)
            for t, n, s in specs]


def host_counts(graph, queries):
    """Reference counts from the host matcher (frontier enumeration,
    counting only, the engine's default result limit)."""
    from repro.core.matcher import GM, GMOptions
    gm = GM(graph)
    opts = GMOptions(enum_method="frontier", materialize=False)
    return [gm.match(q, options=opts).count for q in queries]


def backend_phase() -> dict:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeFailure(f"no TPU found: JAX backend is {backend!r}")
    from repro.jaxgm.frontier import resolve_mode
    from repro.kernels.ops import _resolve
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    mode, impl = resolve_mode(), _resolve("auto")
    log(f"backend={backend} device_kind={device['kind']} "
        f"devices={device['count']} frontier_mode={mode} ops_impl={impl}")
    check(mode == "pallas", f"frontier mode {mode!r}, want 'pallas'")
    check(impl == "pallas", f"ops impl {impl!r}, want 'pallas'")
    return device


def served_phase(graph, queries, want) -> None:
    """Serve ``queries`` through ``QueryServer(graph)``; ``want`` are the
    host counts."""
    from repro.launch.serve import QueryServer
    server = QueryServer(graph)
    eng = server.engine
    # keep each request's served result: the last execute_many answer
    # for its query object is the one the server accepted
    served = {}
    execute_many = eng.execute_many

    def recording(queries_, **kw):
        out = execute_many(queries_, **kw)
        served.update((id(q), r) for q, r in zip(queries_, out))
        return out

    eng.execute_many = recording
    # set-up outside the served window: label build, device graph upload,
    # and the AOT compile of the batch's vmapped pipeline (the server
    # would otherwise split its first batch as a straggler and recompile)
    t0 = time.perf_counter()
    eng.context(graph).ensure_labels()
    t1 = time.perf_counter()
    jgm = eng._resident(graph).jgm()
    t2 = time.perf_counter()
    jgm.prepare_batch(queries)
    log(f"set-up: labels {t1 - t0:.2f} s, device graph "
        f"(n_pad={jgm.dg.n_pad}) {t2 - t1:.2f} s, "
        f"batch-{len(queries)} compile {jgm.compile_s:.2f} s")
    for rid, q in enumerate(queries):
        check(server.submit(rid, q), f"request {rid} rejected")
    t0 = time.perf_counter()
    server.drain()
    wall = time.perf_counter() - t0
    exact_on_device = 0
    for rid, q in enumerate(queries):
        r = server.journal[rid]
        res = served[id(r.query)]
        st = res.stats
        log(f"served rid={rid} {q.name} backend={st.backend} "
            f"enum={st.enum_method} count={r.count} host={want[rid]} "
            f"overflow={r.overflowed} wall_ms={st.total_s * 1e3:.1f} "
            f"degradations={st.degradations}")
        check(r.status == "done", f"request {rid} ended {r.status!r}")
        check(r.count == want[rid],
              f"request {rid}: count {r.count} != host {want[rid]}")
        check(not st.degradations,
              f"request {rid} degraded: {st.degradations}")
        exact_on_device += st.backend == "device" and not r.overflowed
    counters = eng.counters
    log(f"served {len(queries)} requests in {wall:.2f} s "
        f"server={dict(server.stats.items())} "
        f"device_exec={counters['device_exec']} "
        f"overflow_fallbacks={counters['overflow_fallbacks']} "
        f"breaker={eng.breaker.state} "
        f"device_retries={eng.breaker.retries}")
    log(f"device matcher dispatches={jgm.calls} "
        f"compile_s={jgm.compile_s:.2f} kernel_s={jgm.kernel_s:.2f}")
    check(counters["device_exec"] > 0, "no device-matcher execution")
    check(exact_on_device >= MIN_DEVICE_EXACT,
          f"{exact_on_device} queries exact on device, "
          f"want >= {MIN_DEVICE_EXACT}")
    check(counters["budget_degradations"] == 0, "host degradations")
    check(eng.breaker.retries == 0, "device retries")
    check(eng.breaker.state == "closed",
          f"breaker {eng.breaker.state!r}")


def resident_phase(graph, queries, want, streamed, streamed_want) -> None:
    from repro.engine import Engine, EngineOptions
    eng = Engine(graph, options=EngineOptions(force_backend="host"))

    def totals():
        """Cumulative resident work: dispatches, host-routed small slabs,
        and the executors' compile and kernel seconds (each query's
        executor is kept on its plan-cache entry)."""
        exs = [e.resident for e in eng._plan_cache._d.values()
               if e.resident is not None]
        return (eng.counters["resident_dispatches"],
                eng.counters["small_frontier_host_routed"],
                sum(e.compile_s for e in exs), sum(e.kernel_s for e in exs))

    last = [totals()]

    def report(tag, q, st, count, host, wall):
        now = totals()
        disp, routed, comp, kern = (a - b for a, b in zip(now, last[0]))
        last[0] = now
        log(f"{tag} {q.name} backend={st.backend} enum={st.enum_method} "
            f"count={count} host={host} truncated={st.truncated} "
            f"wall_ms={wall * 1e3:.1f} resident_bytes={st.resident_bytes} "
            f"dispatches={disp} host_routed_slabs={routed} "
            f"compile_s={comp:.2f} kernel_s={kern:.3f} "
            f"degradations={st.degradations}")
        check(st.enum_method == "frontier-device-resident",
              f"{q.name}: enum {st.enum_method!r}")
        check(disp > 0, f"{q.name}: no resident dispatch")
        check(count == host, f"{q.name}: count {count} != host {host}")
        check(not st.degradations, f"{q.name} degraded: {st.degradations}")

    for q, host in zip(queries, want):
        t0 = time.perf_counter()
        res = eng.execute(q, materialize=False)
        report("resident", q, res.stats, res.count, host,
               time.perf_counter() - t0)
    t0 = time.perf_counter()
    stream = eng.execute_stream(streamed)
    rows = sum(len(chunk) for chunk in stream)
    check(rows == stream.count, f"streamed {rows} rows, count {stream.count}")
    report("streamed", streamed, stream.stats, stream.count, streamed_want,
           time.perf_counter() - t0)
    log(f"resident uploads={eng.counters['resident_uploads']} "
        f"dispatches={eng.counters['resident_dispatches']} "
        f"breaker={eng.breaker.state} device_retries={eng.breaker.retries}")
    check(eng.breaker.retries == 0, "device retries")
    check(eng.breaker.state == "closed", f"breaker {eng.breaker.state!r}")


def main() -> int:
    try:
        device = backend_phase()
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    import jax
    from repro.data.graphs import paper_profile_graph
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    name, scale, seed = GRAPH
    graph = paper_profile_graph(name, scale, seed=seed)
    log(f"graph {name} scale={scale} seed={seed}: {graph.n} nodes, "
        f"{graph.n_edges} edges, built in {time.perf_counter() - t0:.2f} s")
    try:
        served = make_queries(graph, SERVED)
        resident = make_queries(graph, RESIDENT)
        streamed = make_queries(graph, [STREAMED])[0]
        t0 = time.perf_counter()
        want = host_counts(graph, served + resident + [streamed])
        log(f"host GM reference counts in {time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        served_phase(graph, served, want[:len(served)])
        log(f"phase 1 (served path) {time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        resident_phase(graph, resident, want[len(served):-1], streamed,
                       want[-1])
        log(f"phase 2 (resident enumerator) "
            f"{time.perf_counter() - t0:.2f} s")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
