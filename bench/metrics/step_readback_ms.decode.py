"""Host time per decode step to copy the ready logits to the host: the
``mpk.step.readback`` span."""
from bench.spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "mpk.step.readback")
