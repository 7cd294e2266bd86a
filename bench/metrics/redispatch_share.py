"""Serving: share of the window's served requests that the server
re-dispatched (straggler split, worker loss, transient), in percent."""


def read(w):
    served = w.delta("server_served")
    if not served:
        return None
    return 100.0 * w.delta("server_redispatched") / served
