"""Speculation: device-to-host syncs of the decode path (drafting,
prediction, verify and accept) per verify block.  Read only from a program
that also counts ``verify_syncs``: before it, ``host_syncs`` missed the
drafting syncs."""
from perfbench import spans


def read(run):
    if spans.program_total(run, "verify_syncs") is None:
        return None
    return run.ratio("host_syncs", "verify_blocks")
