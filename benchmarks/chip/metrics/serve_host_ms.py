"""Median, over the window's steps, of a ``serve.step`` less the
``serve.sample`` spans inside it: the host's own work in a step, while
the device waits on it.  Also prints the slowest steps with their
children, and the idle gaps by program span, on standard error."""
from benchmarks.chip import spans


def read(rec):
    found = spans.of_record(rec)
    if found:
        spans.report(rec, found)
    return spans.host_ms(found)
