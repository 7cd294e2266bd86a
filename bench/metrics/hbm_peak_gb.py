"""Device: peak bytes in use on the chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(w):
    if w.memory_peak_bytes is None:
        return None
    return w.memory_peak_bytes / 1e9
