"""Device time per megakernel launch, from the profiler trace."""

KERNEL = "mpk_megakernel"


def read(record):
    if record.trace is None:
        return None
    from bench.tracefile import kernel_durations

    d = kernel_durations(record.trace, KERNEL)
    return 1e3 * sum(d) / len(d) if d else None
