"""Operations and bytes, computed from shapes, and the chip's peaks.

Kept with the benchmark so that every change is counted the same way.  ``cfg`` is a
configuration file's ``model`` block (a plain dict).
"""
from __future__ import annotations

from typing import Any, Dict

from perfbench.spec import HERE, load_json

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def expert_bytes(cfg: Dict[str, Any]) -> int:
    """One SwiGLU expert's weights: gate, up and down projections."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"] * DTYPE_BYTES[cfg["dtype"]]


def expert_flops(cfg: Dict[str, Any]) -> int:
    """One routed (token, choice) through one expert: three matmuls."""
    return 2 * 3 * cfg["d_model"] * cfg["moe_d_ff"]


def cache_moe_least_s(cfg: Dict[str, Any], routed: int, distinct: int,
                      tokens: int, pk: Dict[str, Any]) -> float:
    """The least time one ``cache_moe`` call could take on the chip: the
    larger of its operations over peak FLOP/s and its bytes over HBM
    bandwidth.  ``routed`` (token, choice) pairs are computed; ``distinct``
    experts' weights are read once each; ``tokens`` activation rows are
    read and written."""
    act = 2 * tokens * cfg["d_model"] * DTYPE_BYTES[cfg["dtype"]]
    flop_s = routed * expert_flops(cfg) / pk["bf16_flops_per_s"]
    byte_s = (distinct * expert_bytes(cfg) + act) / pk["hbm_bytes_per_s"]
    return max(flop_s, byte_s)


def forward_flops(cfg: Dict[str, Any], context: int) -> int:
    """The target's forward operations for one token that attends to
    ``context`` positions, at the configuration's depth: attention
    projections and scores, the router, k experts and the output head.  The
    draft's work does not count."""
    d, H, Hkv, hd = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                     cfg["head_dim"])
    attn = 2 * d * (H + 2 * Hkv) * hd + 2 * H * hd * d + 2 * 2 * H * hd * context
    moe = 2 * d * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * expert_flops(cfg)
    return cfg["num_layers"] * (attn + moe) + 2 * d * cfg["vocab_size"]
