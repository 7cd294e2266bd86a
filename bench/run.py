#!/usr/bin/env python3
"""Run one benchmark cell once, in one process, on the chip it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), traffic mix (``bench/mixes/<mix>.json``)
and per-layer metric readers (``bench/metrics/<metric>.py``) are files
found by name, so a new cell, mix or metric is new files and entries only.

1. Refuse any backend but TPU (exit 2, no result line).
2. Generate the configuration's graph and the mix's requests from ``--seed``.
3. Build ``QueryServer(graph)`` with its defaults; set up: labels, device
   graph, the compile of the cell's one batch shape, and one warm-up step
   of requests from a stream the window never uses.  The window is served
   by a second ``QueryServer`` with its defaults on the same engine, so
   nothing the warm-up did to the server's own state (its batch size)
   carries into the window.
4. Closed loop: each client submits its next request when its last one is
   answered; the server serves what is pending, one ``step`` at a time.
   The window ends at the first step boundary after ``--seconds`` at
   which every request submitted is done or failed.
5. Check the answers (all of them, or a sample drawn from the seed)
   against the plain reference (``reference.py``), after the program's
   state is freed.
6. Print the compared numbers with their limits on standard error, then
   the result as the last line of standard output.

With ``--trace 1`` the window runs under the JAX profiler and the line
carries the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import graphgen  # noqa: E402
import numpy as np  # noqa: E402
import querygen  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"     # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_trace"
CHECK_SAMPLE = 1000                 # answers compared with the reference


class NoAccelerator(SystemExit):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ------------------------------------------------------------------ cells
def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    mix and metric lists resolved by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes" / f"{w['traffic']}.json")
                     .read_text())

    def applies(m):
        return name in m.get("workloads", [name])
    return {"name": name, "chips": w["chips"], "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(metric: str, root: Path = ROOT):
    """The ``read(window)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- device
def require_tpu(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu":
        print(f"[bench] no TPU: JAX backend is {dev.platform!r} "
              f"({dev.device_kind}); this benchmark measures the chip only",
              file=sys.stderr, flush=True)
        raise NoAccelerator(2)
    if len(devs) < chips:
        print(f"[bench] the cell asks for {chips} chips, JAX finds "
              f"{len(devs)} ({dev.device_kind})", file=sys.stderr, flush=True)
        raise NoAccelerator(2)
    return device


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs built (compiled or loaded from the persistent cache)
    while armed, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


# ----------------------------------------------------------------- window
@dataclass
class Served:
    j: int                  # request index in the run's window stream
    query: querygen.Query
    submitted: float
    answered: Optional[float] = None
    count: Optional[int] = None
    status: str = "queued"
    overflowed: bool = False


@dataclass
class Window:
    """What the per-layer readers read: counters before and after the
    window, the device matcher's own tallies, the reduced trace."""
    cell: dict
    seconds: float = 0.0
    requests: List[Served] = field(default_factory=list)
    before: Dict = field(default_factory=dict)
    after: Dict = field(default_factory=dict)
    jgm_before: tuple = (0, 0.0)
    jgm_after: tuple = (0, 0.0)
    compiles: int = 0
    memory_peak_bytes: Optional[int] = None
    trace: Optional[dict] = None

    def delta(self, key: str) -> float:
        return self.after.get(key, 0) - self.before.get(key, 0)

    def hist_delta(self, key: str):
        """(Δsum, Δcount) of a histogram series."""
        a = self.after.get(key) or {"sum": 0.0, "count": 0}
        b = self.before.get(key) or {"sum": 0.0, "count": 0}
        return a["sum"] - b["sum"], a["count"] - b["count"]

    def latencies_ms(self) -> List[float]:
        return [(r.answered - r.submitted) * 1e3 for r in self.requests
                if r.status == "done"]


def to_pattern(q: querygen.Query):
    from repro.core.query import PatternQuery, QueryEdge
    return PatternQuery(labels=list(q.labels),
                        edges=[QueryEdge(s, d, k) for s, d, k in q.edges],
                        name=q.name)


def serve_window(server, traffic, clients: int, seconds: float,
                 annotate) -> List[Served]:
    """Closed loop over ``clients``; returns every request of the window.
    Steps until every request submitted is done or failed: the server
    gives up on a request only after ``max_attempts``."""
    out: List[Served] = []
    inflight: Dict[int, Served] = {}
    j = 0

    def submit(now):
        nonlocal j
        q = traffic.request(j)
        r = Served(j=j, query=q, submitted=now)
        with annotate("bench.submit"):
            accepted = server.submit(j, to_pattern(q))
        out.append(r)
        if accepted:
            inflight[j] = r
        else:
            r.answered, r.status = now, "failed"
        j += 1

    t0 = time.perf_counter()
    for _ in range(clients):
        submit(t0)
    end = t0 + seconds
    while inflight:
        t = time.perf_counter()
        with annotate("bench.step"):
            served = server.step()
        now = time.perf_counter()
        if now - t > 1.0:
            log(f"slow step: {served} served in {now - t:.2f} s, "
                f"batch size now {server.batch_size}")
        for rid in list(inflight):
            req = server.journal[rid]
            if req.status in ("done", "failed"):
                r = inflight.pop(rid)
                r.answered, r.status = now, req.status
                r.count, r.overflowed = req.count, req.overflowed
                if now < end:
                    submit(now)
    return out


# ------------------------------------------------------------- reference
def sample(requests: List[Served], seed: int) -> List[Served]:
    """The answers to compare: all, or ``CHECK_SAMPLE`` drawn from the
    seed."""
    done = [r for r in requests if r.status == "done"]
    if len(done) <= CHECK_SAMPLE:
        return done
    rng = np.random.default_rng([seed, 2])
    pick = np.sort(rng.choice(len(done), CHECK_SAMPLE, replace=False))
    return [done[i] for i in pick]


def check(requests: List[Served], compared: List[Served], ref,
          cap: int) -> dict:
    """Compare the ``compared`` answers with the reference; an answer is
    correct when it is the true count, or ``cap`` when the true count is
    at least ``cap``.  A request the server gave up on is a failure."""
    mismatched = 0
    for r in compared:
        served = int(r.count)
        stop = cap if served == cap else max(served + 1, 1)
        truth = ref.count(r.query.labels, r.query.edges, stop=stop)
        ok = truth >= cap if served == cap else truth == served
        if not ok:
            mismatched += 1
            log(f"MISMATCH request {r.j} {r.query.name}: served {served}, "
                f"reference {truth}{'+' if truth >= stop else ''}")
    failed = sum(r.status != "done" for r in requests)
    return {"mismatched": {"value": mismatched, "limit": 0},
            "failed": {"value": failed, "limit": 0}}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# -------------------------------------------------------------- metrics
def end_to_end(w: Window, setup_s: float) -> Dict[str, float]:
    lat = w.latencies_ms()
    out = {"setup_s": setup_s,
           "qps": len(lat) / w.seconds if w.seconds > 0 else 0.0}
    if lat:
        out["p50_ms"] = statistics.median(lat)
    if len(lat) >= 2:
        out["p90_ms"] = statistics.quantiles(lat, n=100,
                                             method="inclusive")[89]
    return out


def result_line(device, w: Window, checks, metrics, trace) -> dict:
    attempted = len(w.requests)
    failed = sum(r.status != "done" for r in w.requests)
    dev = dict(device, memory_peak_bytes=w.memory_peak_bytes)
    line = {"correct": passed(checks), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": dev}
    if trace and w.trace:
        dev["busy_s"] = w.trace["busy_s"]
        dev["window_s"] = w.trace["window_s"]
        line["breakdown"] = w.trace["breakdown"]
    line["checks"] = checks
    return line


# ----------------------------------------------------------------- main
def run(cell: dict, seed: int, seconds: float, trace: bool,
        require=require_tpu) -> dict:
    """One run of ``cell``; returns the result line.  ``require`` is the
    device check (tests replace it to run on the CPU)."""
    device = require(cell["chips"])
    import jax
    enable_compile_cache()
    from repro.core.graph import graph_from_edge_list
    from repro.launch.serve import QueryServer
    compiles = CompileCounter()
    cfg, mix = cell["config"], cell["mix"]
    tag = f"{device['kind']} x{device['count']}"
    t = time.perf_counter()
    edges, labels = graphgen.from_config(cfg, seed)
    graph = graph_from_edge_list(edges, labels, num_labels=cfg["labels"])
    log(f"[{tag}] graph {cfg['name']}: {graph.n} nodes, {graph.n_edges} "
        f"edges, {cfg['labels']} labels in {time.perf_counter() - t:.2f} s")
    traffic = querygen.Traffic(mix, graphgen.Csr(graph.n, edges), labels,
                               seed)
    clients = mix["clients"]
    t = time.perf_counter()
    traffic.requests(mix.get("prefetch", 0))
    log(f"[{tag}] set-up: {mix.get('prefetch', 0)} requests drawn in "
        f"{time.perf_counter() - t:.2f} s")

    warm_server = QueryServer(graph)
    eng = warm_server.engine
    t = time.perf_counter()
    eng.context(graph).ensure_labels()
    log(f"[{tag}] set-up: labels {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    jgm = eng._resident(graph).jgm()
    n_pad = jgm.dg.n_pad
    log(f"[{tag}] set-up: device graph n_pad={n_pad} "
        f"{time.perf_counter() - t:.2f} s")
    warm = traffic.requests(clients, querygen.WARMUP)
    t = time.perf_counter()
    if clients == 1:
        jgm.prepare(to_pattern(warm[0]), materialize=False)
    else:
        jgm.prepare_batch([to_pattern(q) for q in warm])
    log(f"[{tag}] set-up: batch-{clients} compile {jgm.compile_s:.2f} s "
        f"(wall {time.perf_counter() - t:.2f} s)")
    t = time.perf_counter()
    for i, q in enumerate(warm):
        warm_server.submit(-1 - i, to_pattern(q))
    warm_server.drain()
    log(f"[{tag}] set-up: warm-up step of {clients} "
        f"{time.perf_counter() - t:.2f} s")
    server = QueryServer(graph, engine=eng)
    setup_s = time.monotonic() - T_START
    log(f"[{tag}] setup_s {setup_s:.2f}")

    w = Window(cell=cell)
    w.before, w.jgm_before = eng.metrics_snapshot(), (jgm.calls, jgm.kernel_s)
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        annotate = jax.profiler.TraceAnnotation
    else:
        from contextlib import nullcontext

        def annotate(_name):
            return nullcontext()
    compiles.armed = True
    t0 = time.perf_counter()
    w.requests = serve_window(server, traffic, clients, seconds, annotate)
    w.seconds = time.perf_counter() - t0
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    w.compiles = compiles.count
    w.after, w.jgm_after = eng.metrics_snapshot(), (jgm.calls, jgm.kernel_s)
    stats = jax.devices()[0].memory_stats() or {}
    w.memory_peak_bytes = stats.get("peak_bytes_in_use")
    log(f"[{tag}] window {w.seconds:.2f} s: {len(w.requests)} requests, "
        f"{sum(r.status == 'done' for r in w.requests)} answered, "
        f"{sum(r.overflowed for r in w.requests)} overflowed, "
        f"{int(w.delta('server_redispatched'))} re-dispatched, "
        f"final batch size {server.batch_size}, "
        f"{w.compiles} programs built")
    if trace:
        import devtrace
        w.trace = devtrace.reduce_dir(TRACE_DIR, device["kind"])

    # free the program's state before the reference runs
    del server, warm_server, eng, jgm
    gc.collect()
    jax.clear_caches()
    from reference import Reference
    t = time.perf_counter()
    ref = Reference(graph.n, edges, labels)
    compared = sample(w.requests, seed)
    checks = check(w.requests, compared, ref, cfg["result_cap"])
    log(f"[{tag}] reference over {len(compared)} of {len(w.requests)} "
        f"requests {time.perf_counter() - t:.2f} s")

    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = reader(m["name"])(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = end_to_end(w, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in vals}
    for name, c in checks.items():
        print(f"[bench] [{tag}] check {name} = {c['value']} "
              f"(limit {c['limit']})", file=sys.stderr, flush=True)
    return result_line(device, w, checks, metrics, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        line = run(cell, args.seed % (1 << 64), args.seconds,
                   bool(args.trace))
    except NoAccelerator as e:
        return e.code
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
