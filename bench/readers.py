"""What the per-layer metric readers share: means over the window's
Program calls and the least time of a decode launch."""
from __future__ import annotations

from typing import Optional


def mean_call_ms(record, kind: str, what=lambda c: c.t1 - c.t0
                 ) -> Optional[float]:
    """Mean of ``what(call)`` over the window's ``kind`` calls, in ms."""
    calls = [c for c in record.calls if c.kind == kind]
    if not calls:
        return None
    return 1e3 * sum(what(c) for c in calls) / len(calls)


def least_launch_s(record, call) -> float:
    """The least time the chip could take for a decode launch: the larger
    of its operations over peak FLOP/s and its bytes over peak
    bandwidth."""
    positions = [p[-1] for p in call.positions]
    flops, nbytes = record.model.decode_launch(record.sizes, positions)
    return max(flops / record.peaks["bf16_flops_per_s"],
               nbytes / record.peaks["hbm_bytes_per_s"])


def window_flops(record) -> int:
    """Model operations of every token fed in the window's calls."""
    tf = record.model.token_flops
    return sum(tf(record.sizes, p) for c in record.calls
               for fed in c.positions for p in fed)
