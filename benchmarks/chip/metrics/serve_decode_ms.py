"""Median, over the window's decoding steps, of the step's
``serve.decode_step`` and ``serve.sample``: from the decode step's
dispatch until the batch's tokens are on the host."""
from benchmarks.chip import spans


def read(rec):
    return spans.decode_ms(spans.of_record(rec))
