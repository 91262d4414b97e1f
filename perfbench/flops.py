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


def attention_flops(cfg: Dict[str, Any], context: int) -> int:
    """One token's attention over ``context`` positions: projections,
    scores and values.

    Grouped-query: q, k, v ``2·d·(H + 2·Hkv)·hd``, out ``2·H·hd·d``, scores
    and values ``2·2·H·hd`` a position.  With ``use_mla``, latent attention
    counted in its published, non-absorbed form, whichever form the program
    decodes in (nope = ``head_dim``, rope = ``rope_head_dim``, v =
    ``v_head_dim``, r = ``kv_lora_rank``): q ``2·d·H·(nope+rope)``, kv_a
    ``2·d·(r+rope)``, kv_b ``2·r·H·(nope+v)``, scores ``2·H·(nope+rope)`` and
    values ``2·H·v`` a position, out ``2·H·v·d``."""
    d, H, hd = cfg["d_model"], cfg["num_heads"], cfg["head_dim"]
    if not cfg.get("use_mla"):
        Hkv = cfg["num_kv_heads"]
        return 2 * d * (H + 2 * Hkv) * hd + 2 * H * hd * d \
            + 2 * 2 * H * hd * context
    if cfg.get("q_lora_rank"):
        raise ValueError("MLA with a compressed query (q_lora_rank > 0) is "
                         "not counted")
    r, rope = cfg["kv_lora_rank"], cfg["rope_head_dim"]
    v = cfg.get("v_head_dim") or hd
    return 2 * d * H * (hd + rope) + 2 * d * (r + rope) \
        + 2 * r * H * (hd + v) + 2 * H * (hd + rope) * context \
        + 2 * H * v * context + 2 * H * v * d


def forward_flops(cfg: Dict[str, Any], context: int) -> int:
    """The target's forward operations for one token that attends to
    ``context`` positions, at the configuration's depth: attention in every
    layer; a SwiGLU FFN of ``d_ff`` in each of the ``first_dense_layers``;
    in the others the router, k routed and ``num_shared_experts`` shared
    experts, each ``moe_d_ff`` wide; and the output head.  Keys a file
    leaves out count as 0 (no dense layers, no shared experts, no MLA).  The
    draft's work does not count."""
    d, L = cfg["d_model"], cfg["num_layers"]
    dense = cfg.get("first_dense_layers", 0)
    moe = 2 * d * cfg["num_experts"] + (
        cfg["num_experts_per_tok"] + cfg.get("num_shared_experts", 0)
    ) * expert_flops(cfg)
    return L * attention_flops(cfg, context) + dense * 2 * 3 * d * cfg["d_ff"] \
        + (L - dense) * moe + 2 * d * cfg["vocab_size"]
