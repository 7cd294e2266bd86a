"""Device matcher: programs built (compiled, or loaded from the
persistent cache) inside the measured window.  Set-up should leave none."""


def read(w):
    return float(w.compiles)
