"""MoE FFN block: top-k gating + static-shaped capacity routing.

Two routing formulations, both fully static (jit/pjit-safe):

* ``route_grouped`` (train / prefill): routing and capacity are resolved
  *per batch row*, so token gathers are ``take_along_axis`` on the sequence
  dim — sharding-local under batch-sharded activations (no token all-gather).
  This is the GShard grouping trick with gather/scatter instead of the dense
  one-hot dispatch einsum, so dispatch memory is O(E·C·d), not O(S·E·C).
* ``route_global`` (decode): tokens are few (= batch), so routing is done on
  the flat token set; compute is a batched per-expert einsum over
  ``[E, C, d]`` with C = ceil(cf·T·k/E) — FLOP overhead is just the capacity
  factor, never E/k.

Expert weights are ``[E, d, f]``; sharding rules put E on the model axis when
it divides (EP) else f (TP) — see models/sharding.py.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init, ffn_forward, init_ffn

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p: Params = {
        "gate": _dense_init(ks[0], (d, E), jnp.float32),
        "wg": _dense_init(ks[1], (E, d, f), dtype),
        "wu": _dense_init(ks[2], (E, d, f), dtype),
        "wd": _dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_ffn(ks[4], d, cfg.num_shared_experts * f, "swiglu", dtype)
    return p


def gate_topk(gate_w: jax.Array, x: jax.Array, k: int,
              norm_topk_prob: bool = True
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x: [..., d] -> (weights [..., k], ids [..., k], probs [..., E], aux).

    Softmax over all experts, then the top-k.  ``norm_topk_prob`` (Mixtral)
    renormalises the k weights to sum to one; without it (DeepSeek-V2) they
    stay the raw softmax probabilities.  Callers pass the config's
    ``norm_topk_prob``.
    aux = switch load-balancing loss (E · mean(frac_routed · mean_prob)).
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), gate_w)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    E = gate_w.shape[-1]
    flat_ids = ids.reshape(-1, k)
    counts = jnp.sum(jax.nn.one_hot(flat_ids, E, dtype=jnp.float32), axis=(0, 1))
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jnp.mean(probs.reshape(-1, E), axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return weights.astype(x.dtype), ids, probs, aux


def _expert_ffn(p: Params, xg: jax.Array, activation: str) -> jax.Array:
    """xg: [..., E, C, d] -> [..., E, C, d] via per-expert FFN (batched einsum)."""
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xg, p["wg"]))
        h = h * jnp.einsum("...ecd,edf->...ecf", xg, p["wu"])
    else:
        h = jax.nn.gelu(jnp.einsum("...ecd,edf->...ecf", xg, p["wu"]))
    return jnp.einsum("...ecf,efd->...ecd", h, p["wd"])


def _dispatch_indices(ids: jax.Array, E: int, C: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """ids: [T, k] -> (idx [E, C] token index per slot, valid [E, C],
    slot_of [T, k] slot each (token,choice) landed in, C if dropped)."""
    T, k = ids.shape
    flat = ids.reshape(-1)                                  # [T*k]
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    # rank within expert group
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank = jnp.arange(T * k) - starts[sorted_e]
    tok = order // k
    idx = jnp.zeros((E, C), jnp.int32).at[sorted_e, rank].set(
        tok.astype(jnp.int32), mode="drop")
    valid = jnp.zeros((E, C), jnp.bool_).at[sorted_e, rank].set(True, mode="drop")
    slot_unsorted = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(rank < C, rank, C).astype(jnp.int32))
    return idx, valid, slot_unsorted.reshape(T, k)


def moe_grouped(p: Params, x: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """Train / prefill path.  x: [B, S, d] -> (y, aux_loss).

    Routing + capacity per batch row (vmapped dispatch), gathers stay local
    to the batch shard.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = int(np.ceil(cfg.capacity_factor * S * k / E))
    C = max(8, min(S, -(-C // 8) * 8))  # round up to 8, cap at S
    weights, ids, _, aux = gate_topk(p["gate"], x, k, cfg.norm_topk_prob)

    idx, valid, slot = jax.vmap(lambda i: _dispatch_indices(i, E, C))(ids)
    #   idx/valid: [B, E, C]; slot: [B, S, k]
    xg = jnp.take_along_axis(x[:, None, :, :],                      # [B,1,S,d]
                             idx[..., None], axis=2)                # [B,E,C,d]
    yg = _expert_ffn(p, xg, cfg.ffn_activation)
    yg = yg * valid[..., None]
    # combine: for each (token, choice), read back from (expert, slot)
    ygp = jnp.pad(yg, ((0, 0), (0, 0), (0, 1), (0, 0)))             # slot C = dropped
    y = _combine(ygp, ids, slot, weights)
    if cfg.num_shared_experts:
        y = y + ffn_forward(p["shared"], x, "swiglu")
    return y.astype(x.dtype), aux


def _combine(ygp: jax.Array, ids: jax.Array, slot: jax.Array,
             weights: jax.Array) -> jax.Array:
    """ygp: [B, E, C+1, d]; ids/slot/weights: [B, S, k] -> y [B, S, d]."""
    B, E, Cp1, d = ygp.shape
    S, k = ids.shape[1], ids.shape[2]
    flat = ygp.reshape(B, E * Cp1, d)
    gidx = ids * Cp1 + slot                                         # [B, S, k]
    per_choice = jnp.take_along_axis(
        flat[:, None, :, :], gidx.reshape(B, 1, S * k)[..., None], axis=2
    ).reshape(B, S, k, d)
    return jnp.sum(per_choice * weights[..., None], axis=2)


def moe_global(p: Params, x: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, jax.Array]:
    """Decode path: drop-free sorted routing + ``lax.ragged_dot`` grouped
    GEMMs.  x: [B, S, d] with tiny B·S (decode / verification blocks).

    FLOPs are exactly T·k·(3·d·f) — no capacity padding, no drops (drops
    would break speculative-decoding losslessness)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    xf = x.reshape(T, d)
    weights, ids, _, aux = gate_topk(p["gate"], xf, k, cfg.norm_topk_prob)
    flat = ids.reshape(-1)                                  # [T*k]
    order = jnp.argsort(flat, stable=True)
    xs = xf[order // k]                                     # [T*k, d] sorted by expert
    group_sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    if cfg.ffn_activation == "swiglu":
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], group_sizes))
        h = h * jax.lax.ragged_dot(xs, p["wu"], group_sizes)
    else:
        h = jax.nn.gelu(jax.lax.ragged_dot(xs, p["wu"], group_sizes))
    ys = jax.lax.ragged_dot(h, p["wd"], group_sizes)        # [T*k, d]
    y = jnp.zeros((T, d), ys.dtype).at[order // k].add(
        ys * weights.reshape(-1)[order][:, None])
    y = y.reshape(B, S, d)
    if cfg.num_shared_experts:
        y = y + ffn_forward(p["shared"], x, "swiglu")
    return y.astype(x.dtype), aux


def moe_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                decode: bool = False) -> Tuple[jax.Array, jax.Array]:
    if decode or x.shape[0] * x.shape[1] <= 4096 and x.shape[1] <= 8:
        return moe_global(p, x, cfg)
    return moe_grouped(p, x, cfg)


def moe_ref(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Oracle: dense per-token loop over selected experts (no capacity drop).
    Used by tests to validate the routed paths."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    weights, ids, _, _ = gate_topk(p["gate"], x, k, cfg.norm_topk_prob)
    xf = x.reshape(-1, d)
    wf = weights.reshape(-1, k)
    idf = ids.reshape(-1, k)
    out = jnp.zeros_like(xf)
    for e in range(E):
        if cfg.ffn_activation == "swiglu":
            h = jax.nn.silu(xf @ p["wg"][e]) * (xf @ p["wu"][e])
        else:
            h = jax.nn.gelu(xf @ p["wu"][e])
        ye = h @ p["wd"][e]
        wsel = jnp.sum(jnp.where(idf == e, wf, 0.0), axis=1)
        out = out + ye * wsel[:, None]
    y = out.reshape(B, S, d)
    if cfg.num_shared_experts:
        y = y + ffn_forward(p["shared"], x, "swiglu")
    return y.astype(x.dtype)
