"""Host span per ``Program.step``: inputs written, one kernel launch,
logits on the host."""
from bench.readers import mean_call_ms


def read(record):
    return mean_call_ms(record, "step")
