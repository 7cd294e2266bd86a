"""Device matcher: mean host time per ``JaxGM`` encode (transitive
reduction, query tensors, stack and upload of a batch), from the exact
sum and count of ``serve_phase_seconds{phase="jaxgm.encode"}``."""


def read(w):
    s, n = w.hist_delta('serve_phase_seconds{phase="jaxgm.encode"}')
    if not n:
        return None
    return 1e3 * s / n
