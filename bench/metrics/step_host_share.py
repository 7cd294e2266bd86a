"""Serving: share of the window the server's steps spent off the fenced
device dispatch, in percent: the sum of ``serve_phase_seconds
{phase="host"}`` (each step's wall minus its ``jaxgm.dispatch``) over
the window's seconds."""


def read(w):
    s, n = w.hist_delta('serve_phase_seconds{phase="host"}')
    if not n or w.seconds <= 0:
        return None
    return 100.0 * s / w.seconds
