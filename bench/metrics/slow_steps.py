"""Serving: steps in the window longer than four times the server's
running median step (``server_slow_steps``)."""


def read(w):
    if "server_slow_steps" not in w.after:
        return None
    return float(w.delta("server_slow_steps"))
