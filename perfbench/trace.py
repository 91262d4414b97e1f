"""Reduction of a profiler trace to device busy time, per-op time, kernel
events and the longest idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but JAX.
Device planes are named ``/device:<PLATFORM>:<n>``; their ``XLA Ops`` line
holds one event per operation run on the device.  Host planes
(``/host:...``) hold the runtime's host-side events, used to name what the
host was doing during each idle gap.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
Interval = Tuple[int, int]                     # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    dur_ns: int


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]               # device plane -> its ops
    host: List[Op]                             # every host-side event

    def ops(self) -> List[Op]:
        return [o for ops in self.devices.values() for o in ops]


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [Op(e.name, int(e.start_ns), int(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [Op(e.name, int(e.start_ns), int(e.duration_ns))
                     for line in plane.lines for e in line.events]
    return Trace(devices, host)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    per = [sum(e - s for s, e in union((o.start_ns, o.start_ns + o.dur_ns)
                                       for o in ops))
           for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def short(name: str) -> str:
    """An HLO op's event name up to its result type: "%cache_moe.2 =
    bf16[7,16,14336]" of the whole instruction text."""
    return name.split("{", 1)[0].strip()


def top_ops(ops: Sequence[Op], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations (by short name) with the most device time, in
    seconds."""
    tot: Dict[str, int] = {}
    for o in ops:
        k = short(o.name)
        tot[k] = tot.get(k, 0) + o.dur_ns
    return [(k, v / 1e9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest gaps between operations on the first device, each
    named by the host event that overlaps it most (``idle`` if none)."""
    if not trace.devices:
        return []
    ops = next(iter(trace.devices.values()))
    busy = union((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:n]
    host = sorted(trace.host, key=lambda o: o.start_ns)
    out = []
    for length, s, e in gaps:
        best: Optional[Op] = None
        best_overlap = 0
        for o in host:
            if o.start_ns >= e:
                break
            ov = min(e, o.start_ns + o.dur_ns) - max(s, o.start_ns)
            if ov > best_overlap or (ov == best_overlap and best is not None
                                     and ov > 0 and o.dur_ns < best.dur_ns):
                best, best_overlap = o, ov
        out.append((best.name if best is not None else "idle", length / 1e9))
    return out

