"""The whole serving step's share of the chip's peak: model operations of
every token fed in the traced window (prefill and decode) over the
window's length times the peak bf16 FLOP/s."""
from bench.readers import window_flops


def read(record):
    if record.trace is None or not record.calls:
        return None
    lo, hi = record.trace.window()
    return 100.0 * window_flops(record) / (
        (hi - lo) * record.peaks["bf16_flops_per_s"])
