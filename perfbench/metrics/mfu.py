"""Whole step: delivered tokens' forward operations per second over the
chip's peak (moves tokens_per_s)."""
from perfbench import mfu_common


def read(run):
    return mfu_common.delivered(run, skip_first=False)
