"""Device: share of the traced window in which no operation ran on the
chip (averaged over the chips used)."""


def read(w):
    if not w.trace or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
