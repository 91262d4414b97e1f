"""Decoder-only LM assembly: dense / moe / ssm / hybrid families.

Layers with identical structure are stacked and driven by ``lax.scan`` (one
compiled body regardless of depth — the MaxText pattern), with optional
``jax.checkpoint`` remat per layer.  Three modes share one code path:

* ``train``    full sequence, no cache, returns (logits, aux_loss)
* ``prefill``  full sequence, fills caches
* ``decode``   one token, consumes + updates caches; optionally returns the
               per-layer gate-input taps the SP-MoE predictor feeds into the
               target model's gating networks.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, kind: str, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"ln1": L.init_rms_norm(cfg.d_model, dtype),
                "mamba": M.init_mamba(ks[0], cfg, dtype)}
    p: Params = {"ln1": L.init_rms_norm(cfg.d_model, dtype),
                 "ln2": L.init_rms_norm(cfg.d_model, dtype)}
    if cfg.use_mla:
        p["attn"] = L.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    if kind == "moe":
        p["moe"] = MOE.init_moe(ks[1], cfg, dtype)
    else:
        p["ffn"] = L.init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_activation, dtype)
    return p


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
                     dtype) -> Params:
    if kind == "mamba":
        return M.init_mamba_cache(cfg, batch, dtype)
    if cfg.use_mla:
        return L.init_mla_cache(cfg, batch, max_seq, dtype)
    return L.init_kv_cache(cfg, batch, max_seq, dtype)


def block_apply(p: Params, x: jax.Array, kind: str, cfg: ModelConfig,
                mode: str, cache: Optional[Params], pos,
                positions: Optional[jax.Array]
                ) -> Tuple[jax.Array, Optional[Params], jax.Array, jax.Array]:
    """-> (x_out, new_cache, aux_loss, gate_input_tap)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, cache = M.mamba_decode(p["mamba"], h, cache, cfg)
        else:
            y = M.mamba_forward(p["mamba"], h, cfg)
            if mode == "prefill":
                cache = _mamba_prefill_cache(p, h, cfg)
        x = x + y
        return x, cache, aux, x
    # attention half
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if mode == "decode":
        if cfg.use_mla:
            a, cache = L.mla_decode(p["attn"], h, cache, pos, cfg)
        else:
            a, cache = L.attention_decode(p["attn"], h, cache, pos, cfg)
    else:
        if cfg.use_mla:
            a = L.mla_forward(p["attn"], h, cfg, positions)
        else:
            a = L.attention_forward(p["attn"], h, cfg, positions)
        if mode == "prefill":
            cache = _attn_prefill_cache(p, h, cfg, cache, positions)
    x = x + a
    # ffn half
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        # serving paths (decode AND prefill) must be drop-free: capacity
        # drops would corrupt the KV-cache-vs-decode equivalence that
        # speculative-decoding losslessness rests on.  Training keeps
        # capacity-based routing (standard, differentiable-drop regime).
        y, aux = MOE.moe_forward(p["moe"], h2, cfg, decode=(mode != "train"))
    else:
        y = L.ffn_forward(p["ffn"], h2, cfg.ffn_activation)
    x = x + y
    return x, cache, aux, h2       # tap = gate input (SP-MoE predictor input)


def _attn_prefill_cache(p, h, cfg: ModelConfig, cache, positions):
    """Recompute k/v (cheap vs attention) and write them into the cache."""
    B, S, _ = h.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.use_mla:
        c_kv, k_rope = L.mla_latent(p["attn"], h, positions, cfg)
        cache = dict(cache)
        cache["c_kv"] = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv, (0, 0, 0))
        cache["k_rope"] = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, 0, 0))
        cache["pos_map"] = jax.lax.dynamic_update_slice(
            cache["pos_map"], jnp.arange(S, dtype=jnp.int32), (0,))
        return cache
    k = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"]), positions, cfg.rope_theta)
    v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
    W = cache["k"].shape[1]            # ring size (window + margin for SWA)
    if cfg.sliding_window and S > W:   # rolling buffer keeps the last W tokens
        k, v = k[:, -W:], v[:, -W:]
        # rolled so that slot (pos % W) layout matches decode-side indexing
        shift = (S % W)
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
        pos_map = (S - W) + jnp.mod(jnp.arange(W) - S, W).astype(jnp.int32)
        S = W
    else:
        pos_map = jnp.where(jnp.arange(cache["pos_map"].shape[0]) < S,
                            jnp.arange(cache["pos_map"].shape[0]), -1).astype(jnp.int32)
    return {"k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
            "pos_map": pos_map}


def _mamba_prefill_cache(p, h, cfg: ModelConfig):
    """Run the pieces needed to produce (ssm_state, conv window) after h."""
    Bsz, S, _ = h.shape
    d_in, H, N, conv_ch = M._dims(cfg)
    zxbcdt = jnp.einsum("bsd,dp->bsp", h, p["mamba"]["in_proj"])
    z, xs, Bm, Cm, dt = M._split(zxbcdt, cfg)
    xBC_pre = jnp.concatenate([xs, Bm, Cm], -1)
    xBC = M._causal_conv(xBC_pre, p["mamba"]["conv_w"], p["mamba"]["conv_b"])
    xs2, Bm2, Cm2 = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    xh = xs2.reshape(Bsz, S, H, cfg.ssm_head_dim)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["mamba"]["dt_bias"])
    A = -jnp.exp(p["mamba"]["A_log"])
    chunk = min(cfg.ssm_chunk, S)
    if S % chunk:
        padn = chunk - S % chunk
        xh = jnp.pad(xh, ((0, 0), (0, padn), (0, 0), (0, 0)))
        dtf = jnp.pad(dtf, ((0, 0), (0, padn), (0, 0)))
        Bm2 = jnp.pad(Bm2, ((0, 0), (0, padn), (0, 0)))
        Cm2 = jnp.pad(Cm2, ((0, 0), (0, padn), (0, 0)))
    from repro.kernels.ref import ssd_ref
    _, final_state = ssd_ref(xh, dtf, A, Bm2, Cm2, chunk)
    W = cfg.ssm_conv_width
    convwin = jnp.pad(xBC_pre, ((0, 0), (W - 1, 0), (0, 0)))[:, -(W - 1):, :] \
        if S >= 1 else jnp.zeros((Bsz, W - 1, conv_ch), h.dtype)
    return {"ssm": final_state.astype(jnp.float32), "conv": convwin}


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

class DecoderLM:
    """Families: dense, moe, ssm, hybrid, vlm (vlm adds patch inputs)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # -- structure ----------------------------------------------------------
    def _stacks(self):
        """Layer layout: list of (name, kind, count, shared)."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            groups = cfg.num_layers // cfg.attn_every
            tail = cfg.num_layers % cfg.attn_every
            out = [("mamba_groups", "mamba", (groups, cfg.attn_every - 1), False),
                   ("shared_attn", "dense", 1, True)]
            if tail:
                out.append(("tail", "mamba", tail, False))
            return out
        if cfg.family == "ssm":
            return [("layers", "mamba", cfg.num_layers, False)]
        if cfg.is_moe:
            out = []
            if cfg.first_dense_layers:
                out.append(("dense_layers", "dense", cfg.first_dense_layers, False))
            out.append(("layers", "moe", cfg.num_moe_layers, False))
            return out
        return [("layers", "dense", cfg.num_layers, False)]

    def init(self, key) -> Params:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: Params = {
            "wte": L._dense_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype, scale=np.sqrt(cfg.d_model)),
            "ln_f": L.init_rms_norm(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["head"] = L._dense_init(keys[1], (cfg.d_model, cfg.vocab_size), dtype)
        ki = 2
        for name, kind, count, shared in self._stacks():
            if shared:
                params[name] = init_block(keys[ki], kind, cfg, dtype)
            elif isinstance(count, tuple):
                g, per = count
                ks = jax.random.split(keys[ki], g * per).reshape(g, per, -1)
                params[name] = jax.vmap(jax.vmap(
                    lambda k: init_block(k, kind, cfg, dtype)))(ks)
            else:
                ks = jax.random.split(keys[ki], count)
                params[name] = jax.vmap(
                    lambda k: init_block(k, kind, cfg, dtype))(ks)
            ki += 1
        return params

    def init_cache(self, batch: int, max_seq: int) -> Params:
        cfg, dtype = self.cfg, self.dtype
        cache: Params = {}
        for name, kind, count, shared in self._stacks():
            one = lambda: init_block_cache(kind, cfg, batch, max_seq, dtype)
            if shared:
                g = cfg.num_layers // cfg.attn_every
                cache[name] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (g,) + x.shape).copy(), one())
            elif isinstance(count, tuple):
                g, per = count
                cache[name] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (g, per) + x.shape).copy(), one())
            else:
                cache[name] = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (count,) + x.shape).copy(), one())
        return cache

    # -- scanned stack application -------------------------------------------
    def _apply_stack(self, name, kind, shared, lp, x, mode, cache, pos,
                     positions, collect_taps):
        cfg = self.cfg

        def body(carry, xs):
            x, aux = carry
            blockp, blockc = xs
            xo, nc, a, tap = block_apply(blockp, x, kind, cfg, mode, blockc,
                                         pos, positions)
            tap_out = tap if collect_taps else jnp.zeros((), x.dtype)
            return (xo, aux + a), (nc, tap_out)

        body_fn = _maybe_remat(body, cfg, mode)

        if shared:
            # shared weights applied at each site; caches stacked per site
            def sbody(carry, xs):
                x, aux = carry
                blockc = xs
                xo, nc, a, tap = block_apply(lp, x, kind, cfg, mode, blockc,
                                             pos, positions)
                return (xo, aux + a), (nc, tap if collect_taps else jnp.zeros((), x.dtype))
            sfn = _maybe_remat(sbody, cfg, mode)
            (x, aux), (ncache, taps) = jax.lax.scan(sfn, (x, jnp.zeros((), jnp.float32)), cache)
            return x, aux, ncache, taps
        (x, aux), (ncache, taps) = jax.lax.scan(
            body_fn, (x, jnp.zeros((), jnp.float32)), (lp, cache))
        return x, aux, ncache, taps

    def _run(self, params: Params, x: jax.Array, mode: str,
             cache: Optional[Params], pos, positions,
             collect_taps: bool = False):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_cache: Params = {}
        taps: Dict[str, jax.Array] = {}
        stacks = self._stacks()
        if cfg.family == "hybrid":
            # interleave: scan over groups of (per-group mamba scan + shared attn)
            gp = params["mamba_groups"]
            gcache = (cache or {}).get("mamba_groups")
            acache = (cache or {}).get("shared_attn")
            groups = cfg.num_layers // cfg.attn_every
            if gcache is None:
                gcache = jnp.zeros((groups, cfg.attn_every - 1), jnp.float32)
                acache = _broadcast_none(groups)

            def group_body(carry, xs):
                x, aux = carry
                gparams, gc, ac = xs

                def mbody(c, mxs):
                    xx, a = c
                    bp, bc = mxs
                    xo, nc, al, _ = block_apply(bp, xx, "mamba", cfg, mode, bc, pos, positions)
                    return (xo, a + al), nc
                (x, aux), ngc = jax.lax.scan(mbody, (x, aux), (gparams, gc))
                x, nac, al, _ = block_apply(params["shared_attn"], x, "dense",
                                            cfg, mode, ac, pos, positions)
                return (x, aux + al), (ngc, nac)

            gfn = _maybe_remat(group_body, cfg, mode)
            (x, aux_total), (ngc, nac) = jax.lax.scan(
                gfn, (x, aux_total), (gp, gcache, acache))
            new_cache["mamba_groups"], new_cache["shared_attn"] = ngc, nac
            if "tail" in params:
                tc = (cache or {}).get("tail", _none_like(params["tail"], None))
                x, aux, ntc, _ = self._apply_stack("tail", "mamba", False,
                                                   params["tail"], x, mode, tc,
                                                   pos, positions, False)
                aux_total += aux
                new_cache["tail"] = ntc
        else:
            for name, kind, count, shared in stacks:
                scache = (cache or {}).get(name)
                if scache is None:
                    n = count if not shared else cfg.num_layers // cfg.attn_every
                    scache = _broadcast_none(n)
                x, aux, ncache, tp = self._apply_stack(
                    name, kind, shared, params[name], x, mode, scache, pos,
                    positions, collect_taps)
                aux_total += aux
                new_cache[name] = ncache
                if collect_taps:
                    taps[name] = tp
        return x, aux_total, new_cache, taps

    # -- public API -----------------------------------------------------------
    def forward(self, params: Params, tokens: jax.Array,
                patch_embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
        """train-mode full forward.  tokens: [B,S] -> (logits [B,S,V], aux)."""
        cfg = self.cfg
        x = jnp.take(params["wte"], tokens, axis=0)
        if cfg.family == "vlm":
            assert patch_embeds is not None
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        x, aux, _, _ = self._run(params, x, "train", None, None, None)
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._head(params, x)
        if cfg.family == "vlm":
            logits = logits[:, patch_embeds.shape[1]:]
        return logits, aux

    def prefill(self, params: Params, tokens: jax.Array, max_seq: int,
                patch_embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
        """Fill caches with a prompt; return (last-position logits, cache)."""
        cfg = self.cfg
        x = jnp.take(params["wte"], tokens, axis=0)
        if cfg.family == "vlm" and patch_embeds is not None:
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        cache = self.init_cache(tokens.shape[0], max_seq)
        x, _, cache, _ = self._run(params, x, "prefill", cache, None, None)
        x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
        return self._head(params, x)[:, 0], cache

    def decode_step(self, params: Params, cache: Params, tokens: jax.Array,
                    pos, collect_taps: bool = False):
        """tokens: [B,Sq] at positions pos..pos+Sq-1 (Sq>1 = speculative
        verification block) -> (logits [B,Sq,V], new_cache, taps)."""
        cfg = self.cfg
        x = jnp.take(params["wte"], tokens, axis=0)
        x, _, cache, taps = self._run(params, x, "decode", cache, pos, None,
                                      collect_taps)
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._head(params, x)
        return logits, cache, taps

    def _head(self, params: Params, x: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, params["wte"])
        return jnp.einsum("bsd,dv->bsv", x, params["head"])


def _maybe_remat(fn, cfg, mode):
    """Per-layer remat: full recompute, or selective (matmul outputs saved,
    elementwise recomputed — ~0 extra FLOPs, moderate extra memory)."""
    if not (cfg.remat and mode == "train"):
        return fn
    if cfg.remat_policy == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _none_like(tree, leading):
    """Cache placeholder for cacheless modes: scan needs a pytree of xs with a
    matching leading dim; use zeros of shape [n] (ignored by train mode)."""
    first = jax.tree.leaves(tree)[0]
    n = first.shape[0]
    return _broadcast_none(n)


def _broadcast_none(n):
    return jnp.zeros((n,), jnp.float32)
