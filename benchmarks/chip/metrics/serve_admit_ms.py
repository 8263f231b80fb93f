"""Median time of one admission in the window (``serve.admit``): the
B=1 prefill's dispatch, the wait for its first token on the host, and the
dispatch of the insert into the batch."""
from benchmarks.chip import spans


def read(rec):
    return spans.admit_ms(spans.of_record(rec))
