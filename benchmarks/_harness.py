"""Shared measurement + baseline-JSON harness for the ``bench_*`` modules.

One place for the three things every benchmark used to hand-roll:

* **timing** — :func:`measure` runs a callable N times, recording every
  repeat as a child span of one :class:`repro.obs.Tracer` tree, and
  returns median/min seconds plus the trace (so a benchmark can print the
  same phase tree the engine's ``profile=True`` produces).  The legacy
  :func:`timeit` (median microseconds) is a thin wrapper kept for the
  per-figure modules.
* **rows** — :class:`Row` is the common ``name,us_per_call,derived`` CSV
  record consumed by ``run.py``.
* **baselines** — :func:`write_json` emits the machine-readable
  ``BENCH_*.json`` files with a ``schema_version`` field so downstream
  tooling (CI comparisons, the profile smoke check) can detect layout
  changes, and :func:`bench_main` is the shared argparse front end for the
  modules that write them.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from repro.obs import Span, Tracer, render_trace

__all__ = ["BENCH_SCHEMA_VERSION", "Row", "Measurement", "measure",
           "timeit", "bench_payload", "write_json", "bench_main",
           "render_trace", "git_sha"]

# bump when the BENCH_*.json layout changes; version 2 added this field,
# version 3 added provenance (git_sha + timestamp) for the regression gate
BENCH_SCHEMA_VERSION = 3


def git_sha(short: int = 12) -> str:
    """Commit SHA of the working tree, or ``"unknown"`` outside a repo.

    Stamped into every baseline so ``BENCH_history.jsonl`` rows are
    attributable to a commit even after the JSON files are overwritten."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", f"--short={short}", "HEAD"],
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: Dict[str, Any] = field(default_factory=dict)

    def csv(self) -> str:
        d = ";".join(f"{k}={v}" for k, v in self.derived.items())
        return f"{self.name},{self.us_per_call:.1f},{d}"


@dataclass
class Measurement:
    """One timed configuration: robust statistics + the repeat span tree."""

    median_s: float
    min_s: float
    trace: Span

    @property
    def median_us(self) -> float:
        return self.median_s * 1e6


def measure(fn: Callable, repeats: int = 3, name: str = "bench",
            warmup: int = 0, **attrs: Any) -> Measurement:
    """Time ``fn`` over ``repeats`` runs (after ``warmup`` untimed ones).

    Every repeat is a child span of one tracer tree, so the caller can
    render or serialize the measurement exactly like an engine trace."""
    for _ in range(warmup):
        fn()
    tr = Tracer(name)
    durs: List[float] = []
    for i in range(repeats):
        with tr.span("rep", i=i) as sp:
            fn()
        durs.append(sp.duration_s)
    root = tr.finish()
    durs_sorted = sorted(durs)
    med = durs_sorted[len(durs_sorted) // 2]
    root.set(median_us=round(med * 1e6, 1), repeats=repeats, **attrs)
    return Measurement(median_s=med, min_s=durs_sorted[0], trace=root)


def timeit(fn: Callable, repeats: int = 3) -> float:
    """Median wall time in microseconds (legacy surface)."""
    return measure(fn, repeats=repeats).median_us


def bench_payload(bench: str, mode: str, rows: List[Row]) -> Dict[str, Any]:
    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        "bench": bench,
        "mode": mode,
        "git_sha": git_sha(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": [{"name": r.name, "us_per_call": round(r.us_per_call, 1),
                  "derived": r.derived} for r in rows],
    }


def write_json(path: str, bench: str, mode: str, rows: List[Row]) -> None:
    with open(path, "w") as f:
        json.dump(bench_payload(bench, mode, rows), f, indent=2,
                  sort_keys=True)
        f.write("\n")


def bench_main(bench: str, run: Callable[..., List[Row]], *,
               default_out: str, quick_default: bool = True,
               device_flag: bool = False,
               argv: Optional[List[str]] = None) -> List[Row]:
    """Shared CLI for the baseline-writing benchmarks: parses
    ``--quick/--full[/--device] --out``, runs, prints the CSV, writes the
    versioned JSON baseline.  ``quick_default`` selects which mode an
    unflagged invocation means (the engine bench defaults quick, the mjoin
    bench defaults full)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes, CI smoke mode")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes")
    if device_flag:
        ap.add_argument("--device", action="store_true",
                        help="also run the frontier-device (Pallas) path")
    ap.add_argument("--out", default=default_out)
    args = ap.parse_args(argv)
    assert not (args.quick and args.full), "--quick and --full conflict"
    quick = (not args.full) if quick_default else args.quick

    from repro.launch import compile_cache
    compile_cache.enable()
    kw: Dict[str, Any] = {"quick": quick}
    if device_flag:
        kw["device"] = args.device
    t0 = time.perf_counter()
    rows = run(**kw)
    dt = time.perf_counter() - t0
    print("name,us_per_call,derived")
    for r in rows:
        print(r.csv())
    mode = "quick" if quick else "full"
    write_json(args.out, bench, mode, rows)
    print(f"# wrote {args.out} ({mode}, {len(rows)} rows, {dt:.1f}s)")
    return rows
