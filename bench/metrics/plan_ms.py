"""Engine and planner: mean parse + plan time per query over the window,
from the exact sums and counts of ``query_phase_seconds``."""


def read(w):
    out = 0.0
    for phase in ("parse", "plan"):
        s, n = w.hist_delta(f'query_phase_seconds{{phase="{phase}"}}')
        if not n:
            return None
        out += s / n
    return out * 1e3
