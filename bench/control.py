#!/usr/bin/env python3
"""The control of the benchmark's check: it has to come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --requests <k>

The configuration guarantees exact counts of homomorphic occurrences.
The control breaks that guarantee: the plain reference stands in the
program's place and counts only injective occurrences (distinct data
nodes for distinct query nodes, as subgraph-isomorphism matchers do).
Its answers to the first ``k`` requests of the cell's window stream (the
requests a run of that seed serves first) go through the same check as a
run's, and the compared numbers are printed beside their limits.  Runs on
the host alone; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import graphgen  # noqa: E402
import querygen  # noqa: E402
import run  # noqa: E402
from reference import Reference  # noqa: E402


def control(cell: dict, seed: int, requests: int) -> dict:
    """The check's numbers for the control's answers."""
    cfg = cell["config"]
    edges, labels = graphgen.from_config(cfg, seed)
    traffic = querygen.Traffic(cell["mix"], graphgen.Csr(cfg["nodes"], edges),
                               labels, seed)
    traffic.requests(cell["mix"]["clients"], querygen.WARMUP)
    ref = Reference(cfg["nodes"], edges, labels)
    served = []
    for j, q in enumerate(traffic.requests(requests)):
        r = run.Served(j=j, query=q, submitted=0.0, answered=0.0,
                       status="done")
        r.count = min(ref.count(q.labels, q.edges, stop=cfg["result_cap"],
                                injective=True), cfg["result_cap"])
        served.append(r)
    return run.check(served, served, ref, cfg["result_cap"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    checks = control(run.load_cell(args.workload), args.seed % (1 << 64),
                     args.requests)
    for name, c in checks.items():
        print(f"[control] check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": run.passed(checks), "checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
