"""Host matcher: share of device-matcher answers that overflowed the
frontier capacity and were recomputed exactly on the host, in percent."""


def read(w):
    dev = w.delta("engine_device_exec")
    if not dev:
        return None
    return 100.0 * w.delta("engine_overflow_fallbacks") / dev
