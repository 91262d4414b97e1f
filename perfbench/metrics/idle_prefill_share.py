"""Admission and prefill: device-idle time while a prefill
(``spmoe.prefill``, its load waves excluded) was the innermost serving
stage, as a % of the traced window."""
from perfbench import spans


def read(run):
    return spans.idle_share(run, ["spmoe.prefill"])
