"""Verify: blocks committed on the fused fast path, over verify blocks."""


def read(run):
    return run.ratio("fast_blocks", "verify_blocks")
