"""Host time per decode step inside the program: the ``mpk.step`` span of
``MegakernelExecutor.step``, inputs packed to the logits on the host."""
from bench.spans import mean_span_ms


def read(record):
    return mean_span_ms(record, "mpk.step")
