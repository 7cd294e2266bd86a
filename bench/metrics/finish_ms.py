"""Engine and planner: mean time per ``engine.finish`` phase (the device
batch's results, overflow recomputes, duplicates and telemetry events),
from the exact sum and count of
``serve_phase_seconds{phase="engine.finish"}``."""


def read(w):
    s, n = w.hist_delta('serve_phase_seconds{phase="engine.finish"}')
    if not n:
        return None
    return 1e3 * s / n
