"""Expert cache: (layer, expert) lookups found resident, over lookups."""


def read(run):
    return run.ratio("hits", "lookups")
