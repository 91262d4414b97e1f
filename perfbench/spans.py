"""Device-idle time attributed to the program's serving stages.

The serving loop names its stages with host profiler spans
(``jax.profiler.TraceAnnotation`` in ``core/runtime.py``), which land in the
trace on the device ops' clock: ``spmoe.prefill``, ``spmoe.draft``,
``spmoe.verify``, ``spmoe.verify_round`` and ``spmoe.load_wave``.  One
thread opens them all, so they nest.  Each moment belongs to the innermost
stage span open at it; a stage's idle time is the part of its moments in
which no operation ran on the first device.  Any other host event, on any
thread, is never a stage.

A program without these spans or counters (one older than them) reads
``None`` here, as does a trace without a device plane (the CPU).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from perfbench.trace import Interval, Op, Trace, union

STAGES = ("spmoe.prefill", "spmoe.draft", "spmoe.verify",
          "spmoe.verify_round", "spmoe.load_wave")


def owned(spans: Sequence[Op]) -> Dict[str, List[Interval]]:
    """Stage name -> the disjoint intervals in which a span of that name is
    the innermost one open.  A span reaching past its parent's end is cut
    there: spans of one thread nest."""
    out: Dict[str, List[Interval]] = {}
    stack: List[tuple] = []                    # (end_ns, name), outer first
    t = 0

    def own(name: str, s: int, e: int):
        if e > s:
            out.setdefault(name, []).append((s, e))

    for sp in sorted(spans, key=lambda o: (o.start_ns, -o.dur_ns)):
        while stack and stack[-1][0] <= sp.start_ns:
            end, name = stack.pop()
            own(name, t, end)
            t = end
        if stack:
            own(stack[-1][1], t, sp.start_ns)
        t = sp.start_ns
        end = sp.start_ns + sp.dur_ns
        stack.append((min(end, stack[-1][0]) if stack else end, sp.name))
    while stack:
        end, name = stack.pop()
        own(name, t, end)
        t = end
    return out


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_s(tr: Trace) -> Optional[Dict[str, float]]:
    """Stage name -> seconds the first device sat idle while that stage was
    the innermost open; None without a device plane or stage spans."""
    if not tr.devices:
        return None
    spans = [o for o in tr.host if o.name in STAGES]
    if not spans:
        return None
    ops = next(iter(tr.devices.values()))
    busy = union((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    return {name: (sum(e - s for s, e in iv) - overlap_ns(iv, busy)) / 1e9
            for name, iv in owned(spans).items()}


def idle_share(run, names: Sequence[str]) -> Optional[float]:
    """Device-idle time under the stages ``names`` as a % of the traced
    window (the denominator ``device_idle_share`` uses)."""
    if run.trace is None or not run.trace_window_s:
        return None
    idle = idle_s(run.trace)
    if idle is None:
        return None
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / run.trace_window_s


def program_total(run, key: str) -> Optional[float]:
    """Σ of a per-request counter over the window's requests; None where
    the program does not report it."""
    if not run.counters or any(key not in c for c in run.counters.values()):
        return None
    return run.total(key)
