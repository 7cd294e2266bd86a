"""Reduce a JAX profiler trace of the measured window to device metrics.

* the window: from the first to the last of the benchmark's own host
  annotations (``bench.*`` on the host's ``python`` line);
* busy time: the union of the intervals of the operations on each chip's
  ``XLA Ops`` line, clipped to the window, averaged over the chips;
* per-operation device time, the ten largest for ``breakdown``;
* the longest idle gaps of the first chip, each named by the innermost
  host event that covers its middle.

Peaks are a table keyed by ``device_kind``; a kind not in it is an error.
A kernel's roofline share would divide its least time at these peaks by
its device time.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9},
}

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def peaks(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def reduce_profile(pd, kind: str) -> Optional[dict]:
    peaks(kind)
    host, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            devices.append([ev for ln in ops for ev in ln.events])
        elif plane.name.startswith("/host:"):
            host.extend(ev for ln in plane.lines for ev in ln.events)
    marks = [ev for ev in host if ev.name.startswith("bench.")]
    if not devices or not marks:
        return None
    w0 = min(ev.start_ns for ev in marks)
    w1 = max(ev.start_ns + ev.duration_ns for ev in marks)
    busy, per_op = [], {}
    for evs in devices:
        iv = [(max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
              for ev in evs]
        busy.append(_union([(a, b) for a, b in iv if b > a]) / 1e9)
        for ev in evs:
            per_op[ev.name] = per_op.get(ev.name, 0.0) + ev.duration_ns / 1e9
    # idle gaps of the first chip, named by what the host was doing
    first = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in devices[0])
    gaps, end = [], w0
    for a, b in first + [(w1, w1)]:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:10]:
        mid = (a + b) / 2
        cover = [ev for ev in host
                 if ev.start_ns <= mid <= ev.start_ns + ev.duration_ns]
        name = (min(cover, key=lambda ev: ev.duration_ns).name
                if cover else "(no host event)")
        idle.append([name, (b - a) / 1e9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": sum(busy) / len(busy),
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": idle}}


def newest_xplane(directory: Path) -> Optional[Path]:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_dir(directory: Path, kind: str) -> Optional[dict]:
    import jax
    path = newest_xplane(directory)
    if path is None:
        return None
    return reduce_profile(jax.profiler.ProfileData.from_file(str(path)),
                          kind)
