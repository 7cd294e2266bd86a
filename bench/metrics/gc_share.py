"""Host process: share of the window spent in Python garbage-collector
pauses, in percent, from ``process_gc_seconds`` (all generations)."""

SERIES = tuple(f'process_gc_seconds{{generation="{g}"}}' for g in range(3))


def read(w):
    if w.seconds <= 0 or not any(k in w.after for k in SERIES):
        return None
    return 100.0 * sum(w.hist_delta(k)[0] for k in SERIES) / w.seconds
