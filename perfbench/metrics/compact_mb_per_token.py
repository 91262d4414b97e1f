"""Kernel: expert weight bytes that cache_moe's occupancy compaction
gathers (the program's ``compact_bytes``: M = min(S, T*k) experts a call
whose pool S is larger) per token served.  None from a program without the
counter."""
from perfbench import spans


def read(run):
    gathered = spans.program_total(run, "compact_bytes")
    tokens = run.total("tokens")
    if gathered is None or not tokens:
        return None
    return gathered / tokens / 1e6
