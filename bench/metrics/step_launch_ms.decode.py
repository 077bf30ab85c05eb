"""Host time per decode step from the jitted call to its return (dispatch):
the ``mpk.step.launch`` span."""
from bench.spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "mpk.step.launch")
