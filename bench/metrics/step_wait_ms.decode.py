"""Host time per decode step waiting for the logits to be ready on the
device: the ``mpk.step.wait`` span."""
from bench.spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "mpk.step.wait")
