"""Share of the traced window in which no operation ran on the device."""
from benchmarks.chip import trace


def read(rec):
    if rec.trace is None or not rec.trace.device_ops:
        return None
    return 100.0 * trace.idle_share(rec.trace, rec.trace_window)
