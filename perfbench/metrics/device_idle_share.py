"""Device: share of the traced window in which no operation ran."""
from perfbench import trace


def read(run):
    if run.trace is None or not run.trace.devices or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace_window_s)
