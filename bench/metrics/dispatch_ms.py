"""Device matcher: fenced device time per dispatch of the vmapped (or
single-query) matcher, from ``JaxGM.kernel_s`` and ``JaxGM.calls``."""


def read(w):
    calls = w.jgm_after[0] - w.jgm_before[0]
    if not calls:
        return None
    return 1e3 * (w.jgm_after[1] - w.jgm_before[1]) / calls
