"""Verify: device-idle time while a solo verify block or a fused round
(``spmoe.verify`` / ``spmoe.verify_round``, their load waves excluded) was
the innermost serving stage, as a % of the traced window."""
from perfbench import spans


def read(run):
    return spans.idle_share(run, ["spmoe.verify", "spmoe.verify_round"])
