"""Host store to device: device-idle time while an on-demand expert load
(``spmoe.load_wave``) was the innermost serving stage, as a % of the traced
window."""
from perfbench import spans


def read(run):
    return spans.idle_share(run, ["spmoe.load_wave"])
