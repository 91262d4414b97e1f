"""The target's useful operations in a window, over the window and the
chip's peak (the draft's work does not count)."""
from __future__ import annotations

from typing import Optional

from perfbench import flops


def _share(run, total_flops: float) -> Optional[float]:
    if not total_flops:
        return None
    w = run.window
    return 100.0 * total_flops / (w.t_end - w.t0) \
        / run.peaks["bf16_flops_per_s"]


def delivered(run, skip_first: bool) -> Optional[float]:
    """Tokens delivered in the window, each at its own context length; with
    ``skip_first``, only those after each request's first delivery."""
    total = 0.0
    for rid, toks in run.window.tokens.items():
        P = run.sizes[rid][0]
        first = next(d.n for d in run.window.deliveries if d.rid == rid)
        for j in range(first if skip_first else 0, len(toks)):
            total += flops.forward_flops(run.cfg, P + j)
    return _share(run, total)
