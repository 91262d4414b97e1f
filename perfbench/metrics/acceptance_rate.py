"""Speculation: draft tokens the target accepted, over draft tokens."""


def read(run):
    return run.ratio("accepted", "drafted")
