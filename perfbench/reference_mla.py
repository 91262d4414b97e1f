"""Plain float32 reference of DeepSeek-V2-Lite, and its float8 control.

The decoder of DeepSeek-V2 (arXiv:2405.04434; ``DeepseekV2ForCausalLM`` of
the published ``modeling_deepseek.py``, ``q_lora_rank`` null) written in
straightforward ``jax.numpy``, every matmul in float32 at
``Precision.HIGHEST`` on the bfloat16 weights the benchmark made.  No
kernel, cache, batching, absorption or routing capacity; it imports nothing
of the program.  Per layer:

* latent attention (MLA) in the published, non-absorbed form: q = h·wq
  split into nope and rope parts; the latent c_kv = RMSNorm(h·wdkv)
  (``kv_a_layernorm``) and the shared rotary key k_pe = h·wkr
  (``kv_a_proj_with_mqa``); k_nope = c_kv·wuk and v = c_kv·wuv
  (``kv_b_proj``); RoPE with YaRN (``DeepseekV2YarnRotaryEmbedding``) on
  q_pe and k_pe; a causal softmax at (nope + rope)^-1/2 · mscale²; out wo;
* the ``first_dense_layers`` a SwiGLU FFN; every other layer a softmax
  router over all experts in float32 taking the top k, their weights left
  unnormalised unless ``norm_topk_prob`` (``routed_scaling_factor`` is 1),
  plus the shared experts (one SwiGLU of ``num_shared_experts *
  moe_d_ff``);
* the final RMSNorm and the untied head.

Departures, each exact or a relabelling:

* the latent norm has no gain of its own: its gain is a per-channel scale
  right before wuk/wuv, so a model's gain folds into them exactly;
* the rotary halves are rotated in the rotate-half layout; the published
  code de-interleaves q_pe and k_pe first, which is a fixed permutation of
  wq's and wkr's rope columns: a relabelling of random weights;
* wdkv and wkr are the two column blocks of the published
  ``kv_a_proj_with_mqa``.

One layer and one expert at a time (each expert over every position,
weighted zero where not routed), so it fits beside the weights.  The
control is the same forward with every bfloat16 matrix rounded to float8
(e4m3, one scale per output channel) and the float32 router rounded to
bfloat16: the next precision below each stated one.

``cfg`` is a configuration file's ``model`` block; ``w`` is the weights
tree (``wte``, ``head``, ``ln_f``; ``dense_layers`` with ``ln1``, ``ln2``,
``attn`` and ``ffn.{wg,wu,wd}``; ``layers`` with ``ln1``, ``ln2``, ``attn``
and ``moe.{gate,wg,wu,wd,shared}``; ``attn`` is ``{wq,wdkv,wkr,wuk,wuv,
wo}``), each stacked over its layers.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import F32, HI, PAD, _head, _rms, _w

# the fan-in of the latent-attention leaves that weights.make's own table
# does not know
FAN_IN = {"wdkv": "d_model", "wkr": "d_model", "wuk": "kv_lora_rank",
          "wuv": "kv_lora_rank", "wo": "num_heads*v_head_dim"}
# the published ``rope_scaling``'s correction range and original context;
# its ``mscale`` equals ``mscale_all_dim``, so YaRN scales cos and sin by 1
YARN = {"original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(cfg: Dict[str, Any]):
    """(inverse frequencies [rope_head_dim / 2] as float32, softmax scale)
    of the published rotary embedding: with a ``rope_scaling_factor`` above
    1, YaRN's blend of the original and the interpolated frequencies over
    the correction range of ``YARN``'s ``beta_fast`` to ``beta_slow``
    rotations within its original context, and the softmax scaled by
    mscale(factor, ``rope_mscale_all_dim``)²."""
    dim, base = int(cfg["rope_head_dim"]), float(cfg["rope_theta"])
    factor = float(cfg["rope_scaling_factor"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (cfg["head_dim"] + dim) ** -0.5
    if factor <= 1:
        return extra.astype(np.float32), scale
    orig = float(YARN["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(YARN["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(YARN["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    all_dim = float(cfg["rope_mscale_all_dim"])
    if all_dim:
        scale *= _yarn_mscale(factor, all_dim) ** 2
    return inv.astype(np.float32), scale


def _rotate(x, inv):
    """x: [T, H, D] at positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    ang = jnp.arange(T, dtype=F32)[:, None] * inv              # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps", "nope", "scale",
                                             "quant"))
def _attention(x, ln1, a, inv, *, eps, nope, scale, quant):
    h = _rms(x, ln1, eps)
    q = jnp.einsum("td,dhk->thk", h, _w(a["wq"], 0, quant), precision=HI)
    c = jnp.einsum("td,dr->tr", h, _w(a["wdkv"], 0, quant), precision=HI)
    c = _rms(c, jnp.ones((), F32), eps)                 # kv_a_layernorm
    kr = jnp.einsum("td,dk->tk", h, _w(a["wkr"], 0, quant), precision=HI)
    qn, qr = q[..., :nope], _rotate(q[..., nope:], inv)
    kr = _rotate(kr[:, None], inv)                            # [T, 1, rope]
    kn = jnp.einsum("tr,rhk->thk", c, _w(a["wuk"], 0, quant), precision=HI)
    v = jnp.einsum("tr,rhk->thk", c, _w(a["wuv"], 0, quant), precision=HI)
    T, H, _ = q.shape
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (T, H, kr.shape[-1]))], -1)
    qf = jnp.concatenate([qn, qr], -1)
    s = jnp.einsum("qhd,khd->hqk", qf, k, precision=HI) * scale
    i = jnp.arange(T)
    p = jax.nn.softmax(jnp.where((i[None, :] <= i[:, None])[None], s,
                                 -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    return x + jnp.einsum("thk,hkd->td", o, _w(a["wo"], (0, 1), quant),
                          precision=HI)


@functools.partial(jax.jit, static_argnames=("quant",))
def _ffn(h, wg, wu, wd, *, quant):
    g = jnp.einsum("td,df->tf", h, _w(wg, 0, quant), precision=HI)
    u = jnp.einsum("td,df->tf", h, _w(wu, 0, quant), precision=HI)
    return jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, _w(wd, 0, quant),
                      precision=HI)


@functools.partial(jax.jit, static_argnames=("eps", "k", "norm", "quant"))
def _router(x, ln2, gate, *, eps, k, norm, quant):
    """-> (normalised input [T, d], expert weights [T, E] (zero where not
    routed), routing margin [T]: the k-th minus the (k+1)-th gate logit)."""
    h = _rms(x, ln2, eps)
    g = gate.astype(jnp.bfloat16).astype(F32) if quant else gate.astype(F32)
    logits = jnp.einsum("td,de->te", h, g, precision=HI)
    top, ids = jax.lax.top_k(logits, k + 1)
    probs = jax.nn.softmax(logits, -1)
    wk = jnp.take_along_axis(probs, ids[:, :k], -1)
    if norm:
        wk = wk / wk.sum(-1, keepdims=True)
    weights = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], ids[:, :k]].set(wk)
    return h, weights, top[:, k - 1] - top[:, k]


def _layer(tree, l):
    return jax.tree.map(lambda a: a[l], tree)


def forward(w, cfg: Dict[str, Any], tokens: np.ndarray, rows: np.ndarray,
            quant: str = ""):
    """Logits at ``rows`` of ``tokens`` ([T] ids) and, per position, the
    smallest routing margin over the MoE layers."""
    T = len(tokens)
    Tp = -(-T // PAD) * PAD                 # padded tail: causal, so unseen
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = tokens
    x = jnp.take(w["wte"], jnp.asarray(ids), axis=0)
    x = _w(x, 1, quant) if quant else x.astype(F32)
    eps = float(cfg["norm_eps"])
    inv, scale = rope(cfg)
    attn = dict(eps=eps, nope=int(cfg["head_dim"]), scale=scale, quant=quant)
    dense = int(cfg["first_dense_layers"])
    for l in range(dense):
        lw = _layer(w["dense_layers"], l)
        x = _attention(x, lw["ln1"], lw["attn"], inv, **attn)
        f = lw["ffn"]
        x = x + _ffn(_rms(x, lw["ln2"], eps), f["wg"], f["wu"], f["wd"],
                     quant=quant)
    margin = jnp.full((Tp,), jnp.inf, F32)
    for l in range(cfg["num_layers"] - dense):
        lw = _layer(w["layers"], l)
        x = _attention(x, lw["ln1"], lw["attn"], inv, **attn)
        moe = lw["moe"]
        h, weights, m = _router(
            x, lw["ln2"], moe["gate"], eps=eps,
            k=int(cfg["num_experts_per_tok"]),
            norm=bool(cfg["norm_topk_prob"]), quant=quant)
        margin = jnp.minimum(margin, m)
        y = jnp.zeros_like(x)
        for e in range(cfg["num_experts"]):
            y = y + weights[:, e:e + 1] * _ffn(
                h, moe["wg"][e], moe["wu"][e], moe["wd"][e], quant=quant)
        if cfg["num_shared_experts"]:
            s = moe["shared"]
            y = y + _ffn(h, s["wg"], s["wu"], s["wd"], quant=quant)
        x = x + y
    logits = _head(x, w, jnp.asarray(rows), eps=eps, quant=quant)
    return np.asarray(logits), np.asarray(margin)[:T]


def readings(w, cfg: Dict[str, Any], prompt: Sequence[int],
             served: Sequence[int], controls: Sequence[str] = ()
             ) -> Dict[str, np.ndarray]:
    """Per served token, teacher forced along the served tokens: ``gap``,
    the reference's best logit minus the served token's; ``best``, that
    best logit; ``margin``, the routing margin of the position that produced
    it (the smallest k-th minus (k+1)-th gate logit over the MoE layers).
    For ``controls=("fp8",)``, also ``fp8_gap``: the gap of the token that
    the float8 control puts first at the same position."""
    prompt, served = list(prompt), list(served)
    P, n = len(prompt), len(served)
    tokens = np.asarray(prompt + served[:-1], np.int32)
    rows = np.arange(P - 1, P - 1 + n)
    logits, margin = forward(w, cfg, tokens, rows)
    best = logits.max(-1)
    out = {"gap": best - logits[np.arange(n), served], "best": best,
           "margin": margin[rows]}
    for q in controls:
        clog, _ = forward(w, cfg, tokens, rows, quant=q)
        out[f"{q}_gap"] = best - logits[np.arange(n), clog.argmax(-1)]
    return out
