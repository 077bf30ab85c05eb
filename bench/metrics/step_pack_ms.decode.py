"""Host time per decode step to pack the input image and copy it to the
device: the ``mpk.step.pack`` span."""
from bench.spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "mpk.step.pack")
