"""Plain float32 reference of the served model, and its float8 control.

A Mixtral decoder written from the published description in straightforward
``jax.numpy``: RMSNorm, rotary embeddings (rotate-half), grouped-query
causal attention (optionally windowed), a softmax router taking the top-k
experts with their weights renormalised, SwiGLU experts, the output head.
Every matmul runs in float32 at ``Precision.HIGHEST`` on the bfloat16
weights the benchmark made; no kernel, cache, batching or routing capacity.
It imports nothing of the program.  One layer at a time, each expert over
every position (weighted zero where not routed), so it fits beside the
weights.

The control is the same forward with every bfloat16 matrix rounded to
float8 (e4m3, one scale per output channel) and the float32 router rounded
to bfloat16: the next precision below each stated one.

``cfg`` is a configuration file's ``model`` block; ``w`` is the weights
tree (``wte``, ``head``, ``ln_f``, and ``layers`` stacked over depth with
``ln1``, ``ln2``, ``attn.{wq,wk,wv,wo}`` and ``moe.{gate,wg,wu,wd}``).  The
Mixtral configurations name this module as their ``"reference"``; every
leaf is one of ``weights.make``'s own, so it declares no ``FAN_IN``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD = 256                      # sequence lengths are padded to a multiple
FP8_MAX = 448.0                # largest finite float8_e4m3fn


def _w(w, contract, quant):
    """A weight in float32; with ``quant`` ("fp8") rounded to float8 e4m3
    with one scale per output channel (``contract``: the axes a matmul sums
    over)."""
    w = w.astype(F32)
    if quant:
        s = jnp.max(jnp.abs(w), axis=contract, keepdims=True) / FP8_MAX
        s = jnp.where(s > 0, s, 1.0)
        w = (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, theta):
    """x: [T, H, D] at positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv            # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "window",
                                             "quant"))
def _attention(x, lw, l, *, eps, theta, window, quant):
    a = lw["attn"]
    h = _rms(x, lw["ln1"][l], eps)
    q = jnp.einsum("td,dhk->thk", h, _w(a["wq"][l], 0, quant), precision=HI)
    k = jnp.einsum("td,dhk->thk", h, _w(a["wk"][l], 0, quant), precision=HI)
    v = jnp.einsum("td,dhk->thk", h, _w(a["wv"][l], 0, quant), precision=HI)
    q, k = _rope(q, theta), _rope(k, theta)
    T, H, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(D)
    i = jnp.arange(T)
    mask = i[None, :] <= i[:, None]
    if window is not None:
        mask &= i[None, :] > i[:, None] - window
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    return x + jnp.einsum("thk,hkd->td", o, _w(a["wo"][l], (0, 1), quant),
                          precision=HI)


@functools.partial(jax.jit, static_argnames=("eps", "k", "quant"))
def _router(x, lw, l, *, eps, k, quant):
    """-> (normalised input [T, d], expert weights [T, E] (zero where not
    routed), routing margin [T]: the k-th minus the (k+1)-th gate logit)."""
    h = _rms(x, lw["ln2"][l], eps)
    g = lw["moe"]["gate"][l]
    g = g.astype(jnp.bfloat16).astype(F32) if quant else g.astype(F32)
    logits = jnp.einsum("td,de->te", h, g, precision=HI)
    top, ids = jax.lax.top_k(logits, k + 1)
    wk = jax.nn.softmax(top[:, :k], -1)
    weights = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], ids[:, :k]].set(wk)
    return h, weights, top[:, k - 1] - top[:, k]


@functools.partial(jax.jit, static_argnames=("quant",))
def _expert(h, moe, l, e, weight, *, quant):
    g = jnp.einsum("td,df->tf", h, _w(moe["wg"][l, e], 0, quant), precision=HI)
    u = jnp.einsum("td,df->tf", h, _w(moe["wu"][l, e], 0, quant), precision=HI)
    y = jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                   _w(moe["wd"][l, e], 0, quant), precision=HI)
    return weight[:, None] * y


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, w, rows, *, eps, quant):
    h = _rms(x[rows], w["ln_f"], eps)
    return jnp.einsum("td,dv->tv", h, _w(w["head"], 0, quant), precision=HI)


def forward(w, cfg: Dict[str, Any], tokens: np.ndarray, rows: np.ndarray,
            quant: str = ""):
    """Logits at ``rows`` of ``tokens`` ([T] ids) and, per position, the
    smallest routing margin over the layers."""
    T = len(tokens)
    Tp = -(-T // PAD) * PAD                 # padded tail: causal, so unseen
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = tokens
    emb = w["wte"]
    if quant:
        x = _w(jnp.take(emb, jnp.asarray(ids), axis=0), 1, quant)
    else:
        x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(F32)
    lw = w["layers"]
    margin = jnp.full((Tp,), jnp.inf, F32)
    eps = float(cfg["norm_eps"])
    for l in range(cfg["num_layers"]):
        x = _attention(x, lw, l, eps=eps, theta=float(cfg["rope_theta"]),
                       window=cfg["sliding_window"], quant=quant)
        h, weights, m = _router(x, lw, l, eps=eps,
                                k=int(cfg["num_experts_per_tok"]), quant=quant)
        margin = jnp.minimum(margin, m)
        for e in range(cfg["num_experts"]):
            x = x + _expert(h, lw["moe"], l, e, weights[:, e], quant=quant)
    logits = _head(x, w, jnp.asarray(rows), eps=eps, quant=quant)
    return np.asarray(logits), np.asarray(margin)[:T]


def readings(w, cfg: Dict[str, Any], prompt: Sequence[int],
             served: Sequence[int], controls: Sequence[str] = ()
             ) -> Dict[str, np.ndarray]:
    """Per served token, teacher forced along the served tokens: ``gap``,
    the reference's best logit minus the served token's; ``best``, that
    best logit; ``margin``, the routing margin of the position that produced
    it.  For ``controls=("fp8",)``, also ``fp8_gap``: the gap of the token
    that the float8 control puts first at the same position."""
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

