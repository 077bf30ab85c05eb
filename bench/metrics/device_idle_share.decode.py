"""Share of the traced window in which no operation ran on the device."""


def read(record):
    if record.trace is None or not record.trace.device:
        return None
    from bench.tracefile import busy_s

    lo, hi = record.trace.window()
    return 100.0 * (1.0 - busy_s(record.trace) / (hi - lo))
