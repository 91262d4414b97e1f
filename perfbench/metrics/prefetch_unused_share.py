"""Prediction and prefetch: prefetched experts evicted before any use, over
experts prefetched."""


def read(run):
    return run.ratio("prefetch_evicted_unused", "prefetched")
