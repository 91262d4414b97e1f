"""The measured window and the end-to-end arithmetic on its timestamps.

``drive`` consumes ``Engine.serve``'s ``(request_id, token)`` stream until
the window closes and stamps every token on the host clock.  One verify
block's committed tokens are yielded back to back; consecutive tokens of the
same request less than ``GROUP_S`` apart form one delivery, timed by its
first token.  Between two deliveries of a request lies at least one verify
block of device work, far longer than that.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

GROUP_S = 2e-3


@dataclasses.dataclass
class Delivery:
    rid: int
    t: float
    n: int


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    deliveries: List[Delivery]
    tokens: Dict[int, List[int]]


def drive(stream: Iterator[Tuple[str, int]], t0: float, seconds: float,
          clock=time.perf_counter) -> Window:
    """Consume ``stream`` until the first token after ``t0 + seconds``,
    which is dropped; the caller closes the stream."""
    t_end = t0 + seconds
    deliveries: List[Delivery] = []
    tokens: Dict[int, List[int]] = {}
    last: Optional[Tuple[int, float]] = None
    for name, tok in stream:
        t = clock()
        if t > t_end:
            break
        rid = int(name)
        if last is not None and last[0] == rid and t - last[1] < GROUP_S:
            deliveries[-1].n += 1
        else:
            deliveries.append(Delivery(rid, t, 1))
        tokens.setdefault(rid, []).append(int(tok))
        last = (rid, t)
    return Window(t0, t_end, deliveries, tokens)


def by_request(w: Window) -> Dict[int, List[Delivery]]:
    out: Dict[int, List[Delivery]] = {}
    for d in w.deliveries:
        out.setdefault(d.rid, []).append(d)
    return out


def completed(w: Window, max_new: Sequence[int]) -> Dict[int, float]:
    """Requests that delivered all their tokens in the window -> the time of
    their last delivery."""
    return {rid: ds[-1].t for rid, ds in by_request(w).items()
            if sum(d.n for d in ds) >= max_new[rid]}


def tokens_per_s(w: Window) -> float:
    return sum(d.n for d in w.deliveries) / (w.t_end - w.t0)


def tpot_ms(w: Window) -> Optional[float]:
    """Σ over requests of (last - first delivery) over Σ of the tokens
    delivered after each request's first: a ratio of totals."""
    span = toks = 0.0
    for ds in by_request(w).values():
        if len(ds) >= 2:
            span += ds[-1].t - ds[0].t
            toks += sum(d.n for d in ds[1:])
    return 1e3 * span / toks if toks else None


def gaps_s(w: Window) -> List[float]:
    """For every delivery but a request's first: the time since that
    request's previous delivery."""
    return [b.t - a.t for ds in by_request(w).values()
            for a, b in zip(ds, ds[1:])]


def p95(xs: Sequence[float]) -> Optional[float]:
    """95th percentile, linear between order statistics (inclusive)."""
    if len(xs) < 2:
        return None
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


def gap_p95_ms(w: Window) -> Optional[float]:
    v = p95(gaps_s(w))
    return None if v is None else 1e3 * v


def ttft_s(w: Window, max_new: Sequence[int], clients: int) -> Dict[int, float]:
    """First delivery minus admission, per request admitted in the window.
    ``Engine.serve`` admits in list order into ``clients`` slots, each freed
    when a request completes, so request j >= clients is admitted when the
    (j - clients + 1)-th request completes; the first ``clients`` at the
    window's start."""
    done = sorted(completed(w, max_new).values())
    out = {}
    for rid, ds in by_request(w).items():
        j = rid - clients
        admitted = w.t0 if j < 0 else (done[j] if j < len(done) else None)
        if admitted is not None:
            out[rid] = ds[0].t - admitted
    return out


def ttft_ms(w: Window, max_new: Sequence[int], clients: int) -> Optional[float]:
    v = list(ttft_s(w, max_new, clients).values())
    return 1e3 * statistics.median(v) if v else None


def outcome(w: Window, max_new: Sequence[int],
            finish: Dict[int, str]) -> Tuple[int, int]:
    """(attempted, failed).  A request is attempted once it completed in the
    window or the program ended it; one the program ended with any reason
    but ``length`` failed.  ``aborted`` is the harness closing the stream at
    the window's end: such a request counts only if it had delivered all
    its tokens, and otherwise was in flight and counts as neither."""
    done = completed(w, max_new)
    attempted = failed = 0
    for rid, reason in finish.items():
        if reason == "aborted":
            if rid in done:
                attempted += 1
        elif rid in w.tokens or reason != "length":
            attempted += 1
            if reason != "length" or rid not in done:
                failed += 1
    return attempted, failed
