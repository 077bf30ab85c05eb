"""Share of the roofline the megakernel reaches: the least time of a
launch (its operations at peak FLOP/s or its bytes at peak bandwidth,
whichever is longer, from the shapes) over its device time, both
averaged over the window's launches."""
from bench.readers import least_launch_s

KERNEL = "mpk_megakernel"


def read(record):
    steps = [c for c in record.calls if c.kind == "step"]
    if record.trace is None or not steps:
        return None
    from bench.tracefile import kernel_durations

    d = kernel_durations(record.trace, KERNEL)
    if not d:
        return None
    least = sum(least_launch_s(record, c) for c in steps) / len(steps)
    return 100.0 * least / (sum(d) / len(d))
