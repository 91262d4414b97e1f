"""Kernel: cache_moe's roofline share at top-6 of 64 fine-grained experts
over a pool larger than a block's choices, its occupancy compaction counted
with its Pallas stages (moves tpot_ms).

``cache_moe_roofline.decode`` divides the least time by the Pallas stages
alone, which is the whole call where the pool holds at most T·k slots. Here
a 128-slot pool against T·k = 30 choices compacts on every decode call:
the occupied slots' weight rows are gathered before the grid, and that
gather takes most of the call's device time. Its XLA ops carry no kernel
name; they are told apart by the arrays they write. In the TPU program
that ``jnp.take`` over the pool compiles to (compiled for a v5e), those are
fusions, while loops and broadcasts whose output holds a bfloat16 array led
by the pool's slot count S in a layout other than the pool's own (the
pool copied in column pieces) or by the compacted count M = min(S, T·k),
one of its other two dims the expert width f and the other at least 128
(a piece of the weight rows). The slot scatter's loops write the pool's
own shape, the Pallas stages are custom calls and their output is [M, C, f]
with C the per-slot capacity (16 here), so neither is counted; prefill
blocks compact nothing. The denominator is the union of the kernel's and
the compaction's events, so a loop's body events count once.
"""
import re

from perfbench import moe_roofline
from perfbench.trace import union

ARRAY = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
OPCODES = ("fusion", "while", "broadcast")


def split_hlo(name):
    """(output type, opcode) of a device op named by its HLO text,
    '%x = <type> <opcode>(...)...'; a tuple type's layouts nest
    parentheses."""
    _, _, rest = name.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].strip().split("(")[0]
        return rest, ""
    typ, _, tail = rest.partition(" ")
    return typ, tail.split("(")[0]


def compaction_ops(ops, slots, compact, d, f):
    """The ops of ``ops`` that gather ``compact`` of ``slots`` pool slots'
    expert weights ([slots, d, f] and [slots, f, d]); none without
    compaction (a pool of at most ``compact`` slots)."""
    if compact >= slots:
        return []
    pool = {(slots, d, f), (slots, f, d)}
    out = []
    for o in ops:
        typ, opcode = split_hlo(o.name)
        if opcode not in OPCODES:
            continue
        shapes = [tuple(map(int, a)) for a in ARRAY.findall(typ)]
        if pool & set(shapes):
            continue
        if any(a in (slots, compact) and
               ((b == f and c >= 128) or (c == f and b >= 128))
               for a, b, c in shapes):
            out.append(o)
    return out


def read(run):
    if run.trace is None:
        return None
    ops = run.trace.ops()
    kern = moe_roofline.kernel_ops(ops)
    if not kern:
        return None
    cfg = run.cfg
    slots = run.serving["expert_pool_slots"]
    compact = min(slots, (run.serving["draft_len"] + 1)
                  * cfg["num_experts_per_tok"])
    timed = kern + compaction_ops(ops, slots, compact, cfg["d_model"],
                                  cfg["moe_d_ff"])
    busy_ns = sum(e - s for s, e in union(
        (o.start_ns, o.start_ns + o.dur_ns) for o in timed))
    return 100.0 * moe_roofline.least_s(run) / (busy_ns / 1e9)
