"""Jit and compile cache: executables compiled or read from the persistent
cache while the window ran (each one a stall of the serving loop)."""


def read(run):
    return float(len(run.compiles))
