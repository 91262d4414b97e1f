"""Kernel: cache_moe's roofline share in decode traffic (moves tpot_ms)."""
from perfbench import moe_roofline


def read(run):
    return moe_roofline.share(run)
