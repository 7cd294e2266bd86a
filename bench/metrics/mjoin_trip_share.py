"""Device matcher: the frontier MJoin's constraint-loop trips as a share
of its padded slots (``max_q × max_e`` per dispatch), in percent, from
``jaxgm_mjoin_edge_trips`` and ``jaxgm_mjoin_edge_slots``."""


def read(w):
    slots = w.delta("jaxgm_mjoin_edge_slots")
    if not slots:
        return None
    return 100.0 * w.delta("jaxgm_mjoin_edge_trips") / slots
