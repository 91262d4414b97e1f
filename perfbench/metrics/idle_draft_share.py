"""Speculation: device-idle time while drafting, prediction and prefetch
submission (``spmoe.draft``) was the innermost serving stage, as a % of the
traced window."""
from perfbench import spans


def read(run):
    return spans.idle_share(run, ["spmoe.draft"])
