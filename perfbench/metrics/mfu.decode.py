"""Whole step: decode tokens' (after each request's first) forward
operations per second over the chip's peak (moves tpot_ms)."""
from perfbench import mfu_common


def read(run):
    return mfu_common.delivered(run, skip_first=True)
