"""Core layers: RMSNorm, RoPE, GQA/MQA/MLA attention (+KV caches, sliding
window, absorbed MLA decode), FFN variants.

All functions are pure; parameters are plain dicts of jnp arrays.  Naming is
stable because sharding rules (models/sharding.py) key off parameter paths.
Compute dtype follows the input; reductions (softmax, norms) run in f32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rms_normalize(x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm without a gain, computed in f32, returned in x's dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return rms_normalize(x, eps) * weight


def init_rms_norm(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape [head_dim // 2] (f32)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv: Optional[jax.Array] = None) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S] (int32).
    ``inv`` overrides the inverse frequencies (YaRN, :func:`mla_rope_freqs`)."""
    d = x.shape[-1]
    if inv is None:
        inv = rope_freqs(d, theta)                              # [D/2]
    ang = positions.astype(jnp.float32)[..., None] * inv        # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                            # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masked multi-head attention (einsum form, SPMD-friendly)
# ---------------------------------------------------------------------------

def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        mask: Optional[jax.Array], softcap: Optional[float] = None,
        scale: Optional[float] = None) -> jax.Array:
    """q: [B,Sq,H,D]  k: [B,Skv,Hkv,D]  v: [B,Skv,Hkv,Dv]  -> [B,Sq,H,Dv].

    GQA via head-group reshape; mask broadcastable to [B, 1|Hkv, 1|rep, Sq, Skv]
    (True = attend).  Softmax in f32, over scores scaled by ``scale``
    (default 1/sqrt(D)).
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, D)
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(D) if scale is None else scores * scale
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def causal_mask(sq: int, skv: int, window: Optional[int] = None) -> jax.Array:
    """[1,1,1,Sq,Skv] causal (optionally sliding-window) mask.

    Positions are aligned to the *end*: query i sits at absolute position
    skv - sq + i.
    """
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None, None]


# ---------------------------------------------------------------------------
# GQA attention block (dense / mixtral / zamba2 shared / whisper)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, H, hd), dtype),
        "wk": _dense_init(ks[1], (d, Hkv, hd), dtype),
        "wv": _dense_init(ks[2], (d, Hkv, hd), dtype),
        "wo": _dense_init(ks[3], (H, hd, d), dtype),
    }


def attention_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                      positions: Optional[jax.Array] = None,
                      mask: Optional[jax.Array] = None,
                      kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                      use_rope: bool = True) -> jax.Array:
    """Full-sequence attention (train / prefill).  x: [B,S,D]."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is not None:                      # cross-attention
        k, v = kv_override
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if use_rope:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    if (cfg.attn_impl == "kernel" and kv_override is None and mask is None
            and cfg.attn_logit_softcap is None):
        # Pallas flash attention (Mosaic on TPU, interpret elsewhere);
        # handles causal + sliding-window + GQA with blocked online softmax
        from repro.kernels.flash_attention import flash_attention
        import jax as _jax
        out = flash_attention(q, k, v, causal=True,
                              window=cfg.sliding_window,
                              block_q=min(128, S), block_k=min(128, S),
                              interpret=_jax.default_backend() != "tpu")
    else:
        if mask is None and kv_override is None:
            mask = causal_mask(S, k.shape[1], cfg.sliding_window)
        out = mha(q, k, v, mask, cfg.attn_logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# Rolling SWA caches get margin slots beyond the window so a speculative
# verification block (up to this many tokens) never clobbers slots that are
# still inside the window for the block's earlier queries.
SWA_RING_MARGIN = 16


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Dict[str, jax.Array]:
    """KV cache.  Rolling buffer when sliding window is on (mixtral
    long-context).  ``pos_map[s]`` records the absolute position held by slot
    ``s`` (-1 = empty); masks are derived from it, which makes multi-token
    verification blocks and rolling-buffer wraparound uniformly correct.
    """
    seq = (min(max_seq, cfg.sliding_window + SWA_RING_MARGIN)
           if cfg.sliding_window else max_seq)
    shp = (batch, seq, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype),
            "pos_map": jnp.full((seq,), -1, jnp.int32)}


def attention_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                     pos: jax.Array, cfg: ModelConfig,
                     use_rope: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Decode a block of Sq >= 1 tokens at absolute positions pos..pos+Sq-1
    (Sq > 1 = speculative-verification block).  x: [B,Sq,D]; pos: scalar.

    RoPE is applied at write time with the token's absolute position; for
    sliding-window configs the cache is a rolling buffer (slot = pos % W) and
    validity comes from the stored per-slot absolute positions.
    """
    B, Sq, _ = x.shape
    S = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    qpos = pos + jnp.arange(Sq, dtype=jnp.int32)
    if use_rope:
        pp = jnp.broadcast_to(qpos[None, :], (B, Sq))
        q = apply_rope(q, pp, cfg.rope_theta)
        k = apply_rope(k, pp, cfg.rope_theta)
    slots = jnp.mod(qpos, S) if cfg.sliding_window else qpos
    ck = cache["k"].at[:, slots].set(k)
    cv = cache["v"].at[:, slots].set(v)
    pos_map = cache["pos_map"].at[slots].set(qpos)
    # mask: [1,1,1,Sq,S] — slot valid for query i iff it holds a position
    # <= qpos[i] (and within the window for SWA).
    valid = (pos_map[None, :] <= qpos[:, None]) & (pos_map[None, :] >= 0)
    if cfg.sliding_window:
        valid &= pos_map[None, :] > qpos[:, None] - cfg.sliding_window
    mask = valid[None, None, None]
    out = mha(q, ck, cv, mask, cfg.attn_logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, {"k": ck, "v": cv, "pos_map": pos_map}


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2): compressed KV cache + absorbed decode
# ---------------------------------------------------------------------------
#
# As published (DeepSeek-V2, arXiv:2405.04434; ``DeepseekV2Attention`` of
# its modeling_deepseek.py, q_lora_rank null): q = x·wq split into nope and
# rope parts; the latent c_kv = RMSNorm(x·wdkv) (``kv_a_layernorm``) and the
# shared rotary key k_pe = x·wkr; k_nope = c_kv·wuk, v = c_kv·wuv
# (``kv_b_proj``); RoPE with YaRN on q_pe and k_pe; softmax at
# (nope + rope)^-1/2 · mscale².  Departure: the latent norm's gain is not a
# parameter of its own.  It is a per-channel scale right before wuk/wuv, so
# it is folded into them, which is exact; the norm here has no gain.  HF's
# interleaved rope layout is a fixed permutation of wq's and wkr's rope
# columns: a relabelling of the weights, not a departure.


def yarn_mscale(scale: float, mscale: float) -> float:
    """``yarn_get_mscale`` of DeepSeek-V2's modeling_deepseek.py."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


# YaRN's correction range and original context, as DeepSeek-V2-Lite's
# published config.json states them (``rope_scaling``).  Its ``mscale``
# equals ``mscale_all_dim``, so the cos/sin factor mscale(factor, mscale) /
# mscale(factor, mscale_all_dim) of ``DeepseekV2YarnRotaryEmbedding`` is 1.
YARN_ORIGINAL_MAX_POSITION = 4096
YARN_BETA_FAST = 32
YARN_BETA_SLOW = 1


def mla_rope_freqs(cfg: ModelConfig) -> jax.Array:
    """Inverse frequencies [rope_head_dim // 2] of MLA's rotary part.
    With ``rope_scaling_factor`` > 1, YaRN as
    ``DeepseekV2YarnRotaryEmbedding`` computes them: below the correction
    range (``YARN_BETA_FAST`` rotations over the original context) the
    original frequencies, above it (``YARN_BETA_SLOW``) the interpolated
    ones (÷ factor), a linear ramp between.  It acts at every position."""
    dim, base = cfg.rope_head_dim, cfg.rope_theta
    extra = rope_freqs(dim, base)
    factor = cfg.rope_scaling_factor
    if factor <= 1:
        return extra

    def correction_dim(rotations):
        return dim * math.log(YARN_ORIGINAL_MAX_POSITION
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(YARN_BETA_FAST)), 0)
    high = min(math.ceil(correction_dim(YARN_BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times mscale(factor, mscale_all_dim)² with YaRN."""
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_scaling_factor,
                             cfg.rope_mscale_all_dim) ** 2
    return scale


def mla_rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig
             ) -> jax.Array:
    return apply_rope(x, positions, cfg.rope_theta, inv=mla_rope_freqs(cfg))


def init_mla(key, cfg: ModelConfig, dtype) -> Params:
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, vd, r = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, H, nope + rope_d), dtype),
        "wdkv": _dense_init(ks[1], (d, r), dtype),
        "wkr": _dense_init(ks[2], (d, rope_d), dtype),
        "wuk": _dense_init(ks[3], (r, H, nope), dtype),
        "wuv": _dense_init(ks[4], (r, H, vd), dtype),
        "wo": _dense_init(ks[5], (H, vd, d), dtype),
    }


def mla_latent(p: Params, x: jax.Array, positions: jax.Array,
               cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """What MLA caches for x [B,S,d] at ``positions`` (broadcastable to
    [B,S]): the normalised latent c_kv [B,S,r] and the rotated shared key
    k_rope [B,S,rope]."""
    c_kv = rms_normalize(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), cfg.norm_eps)
    k_rope = mla_rope(jnp.einsum("bsd,dk->bsk", x, p["wkr"])[:, :, None, :],
                      positions, cfg)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence MLA (train / prefill): expand the latent, run GQA-style."""
    B, S, _ = x.shape
    nope = cfg.head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = mla_rope(q_rope, positions, cfg)
    c_kv, k_rope = mla_latent(p, x, positions, cfg)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"])
    H = cfg.num_heads
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (B, S, H, cfg.rope_head_dim))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    out = mha(qf, k, v, causal_mask(S, S), scale=mla_softmax_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Dict[str, jax.Array]:
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.rope_head_dim), dtype),
        "pos_map": jnp.full((max_seq,), -1, jnp.int32),
    }


def mla_decode(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
               pos: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-matmul MLA decode: attention runs in the `kv_lora` latent
    space, so per-step cost is O(S·r) instead of O(S·H·head_dim) and the cache
    stays compressed.  Scaling is the full-sequence path's
    (:func:`mla_softmax_scale`).  Handles Sq >= 1 (verification blocks).
    """
    B, Sq, _ = x.shape
    nope = cfg.head_dim
    qpos = pos + jnp.arange(Sq, dtype=jnp.int32)
    pp = jnp.broadcast_to(qpos[None, :], (B, Sq))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])                    # [B,Sq,H,nope+rd]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = mla_rope(q_rope, pp, cfg)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])         # absorb W_uk
    c_new, kr_new = mla_latent(p, x, pp, cfg)
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new, (0, pos, 0))
    pos_map = jax.lax.dynamic_update_slice(cache["pos_map"], qpos, (pos,))
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv) +
              jnp.einsum("bshk,btk->bhst", q_rope, k_rope)).astype(jnp.float32)
    scores = scores * mla_softmax_scale(cfg)
    valid = (pos_map[None, :] <= qpos[:, None]) & (pos_map[None, :] >= 0)
    scores = jnp.where(valid[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv)            # latent context
    ctx = jnp.einsum("bshr,rhk->bshk", ctx_lat, p["wuv"])          # absorb W_uv
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    return out, {"c_kv": c_kv, "k_rope": k_rope, "pos_map": pos_map}


# ---------------------------------------------------------------------------
# FFN variants
# ---------------------------------------------------------------------------

def init_ffn(key, d: int, f: int, activation: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    if activation == "swiglu":
        return {"wg": _dense_init(ks[0], (d, f), dtype),
                "wu": _dense_init(ks[1], (d, f), dtype),
                "wd": _dense_init(ks[2], (f, d), dtype)}
    return {"wu": _dense_init(ks[0], (d, f), dtype),
            "wd": _dense_init(ks[1], (f, d), dtype)}


def ffn_forward(p: Params, x: jax.Array, activation: str) -> jax.Array:
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["wu"])
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x, p["wu"])))
    elif activation == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wu"]))
    else:
        raise ValueError(activation)
    return jnp.einsum("bsf,fd->bsd", h, p["wd"])


def ffn_params_per_layer(cfg: ModelConfig, f: Optional[int] = None) -> int:
    f = f if f is not None else cfg.d_ff
    mats = 3 if cfg.ffn_activation == "swiglu" else 2
    return mats * cfg.d_model * f
