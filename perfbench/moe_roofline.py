"""``cache_moe``'s share of its roofline over a window: the least time the
chip could take for every call the window made, over the device time of the
kernel's events in the trace.

Per request, from its counters: the prompt's prefill is one verify block of
P tokens that routes to min(E, P * k) experts of every MoE layer (the
``first_dense_layers`` have none); each further verify block has
draft_len + 1 tokens, and ``lookups`` counts the distinct (layer, expert)
pairs of every block once.
A fast block that fell back to the slow path is counted once (its useful
work); the device time of the discarded attempt stays in the denominator.
Bytes are the weights of the distinct experts the tokens route to plus the
activations in and out, never the slots the kernel happens to read.
"""
from __future__ import annotations

from typing import Optional

from perfbench import flops

# kernels/cache_moe.py's two Pallas stages (gate/up, down) are the Mosaic
# custom calls of the jitted ``cache_moe``; on a TPU v5e their trace events
# read "%cache_moe.2 = bf16[7,16,14336]{...} custom-call(...),
# custom_call_target="tpu_custom_call", ..." (measured on one v5e chip)
KERNEL = "%cache_moe"
CUSTOM_CALL = "tpu_custom_call"


def kernel_ops(ops):
    return [o for o in ops
            if o.name.startswith(KERNEL) and CUSTOM_CALL in o.name]


def least_s(run) -> float:
    cfg, pk = run.cfg, run.peaks
    L = cfg["num_layers"] - cfg.get("first_dense_layers", 0)
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    T = run.serving["draft_len"] + 1
    total = 0.0
    for rid, c in run.counters.items():
        if not c["verify_blocks"]:
            continue
        P = run.sizes[rid][0]
        pre = min(E, P * k) * L
        total += flops.cache_moe_least_s(cfg, P * k * L, pre, P * L, pk)
        blocks = c["verify_blocks"] - 1
        if blocks:
            # a block of T * k choices is bound by reading >= 1 expert's
            # weights, so the sum of per-block least times is the bytes term
            # of the sums
            total += flops.cache_moe_least_s(
                cfg, blocks * T * k * L, c["lookups"] - pre, blocks * T * L,
                pk)
    return total


def share(run) -> Optional[float]:
    if run.trace is None:
        return None
    kern = sum(o.dur_ns for o in kernel_ops(run.trace.ops()))
    if not kern:
        return None
    return 100.0 * least_s(run) / (kern / 1e9)
