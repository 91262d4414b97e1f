"""Sequence-parallel Mamba2 (SSD) — the collective-bound hillclimb for
mamba2-780m prefill_32k (EXPERIMENTS.md §Perf).

Baseline TP shards ``d_inner`` over the model axis, paying two activation
all-reduces per layer (the dominant roofline term for this small-d_model
arch).  Here instead:

* weights are REPLICATED over the model axis (mamba2-780m is 1.6 GB — fits);
* the SEQUENCE is sharded over the model axis; every pointwise op
  (projections, norms, gating) is shard-local;
* the SSD recurrence crosses shards through two tiny collectives per layer:
    - a width-(W-1) halo exchange (collective-permute) for the causal conv;
    - an all-gather of per-shard (final_state [B,H,P,N], total_decay [B,H])
      followed by a local prefix combine — the cross-shard state is then
      folded in closed form:  y_t += C_t · (state_in ⊙ exp(dA_cum_t)).

Collective bytes per layer drop from O(tokens · d_model) to
O(shards · B · H · P · N) — about 400x for the prefill_32k cell.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.ref import ssd_ref
from repro.models import layers as L
from repro.models import mamba as M

Params = Dict[str, Any]


def _mamba_block_local(p: Params, x: jax.Array, cfg: ModelConfig,
                       axis: str) -> jax.Array:
    """One mamba block on a sequence shard (runs inside shard_map).

    x: [B, S_loc, d].  Cross-shard pieces: conv halo + SSD state prefix.
    """
    Bsz, S, _ = x.shape
    d_in, H, N, conv_ch = M._dims(cfg)
    n_shards = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)

    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    zxbcdt = jnp.einsum("bsd,dp->bsp", h, p["mamba"]["in_proj"])
    z, xs, Bm, Cm, dt = M._split(zxbcdt, cfg)
    xBC_pre = jnp.concatenate([xs, Bm, Cm], -1)

    # --- causal conv with halo from the left neighbour ---
    W = cfg.ssm_conv_width
    halo = xBC_pre[:, -(W - 1):, :]
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    halo_in = jax.lax.ppermute(halo, axis, perm)
    halo_in = jnp.where(idx == 0, jnp.zeros_like(halo_in), halo_in)
    padded = jnp.concatenate([halo_in, xBC_pre], axis=1)
    conv = sum(padded[:, i:i + S, :] * p["mamba"]["conv_w"][i]
               for i in range(W))
    xBC = jax.nn.silu(conv + p["mamba"]["conv_b"])

    xs2, Bm2, Cm2 = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    xh = xs2.reshape(Bsz, S, H, cfg.ssm_head_dim)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["mamba"]["dt_bias"])
    A = -jnp.exp(p["mamba"]["A_log"])

    # --- local SSD with zero initial state ---
    chunk = min(cfg.ssm_chunk, S)
    assert S % chunk == 0, "shard length must be a chunk multiple"
    y_loc, state_loc = ssd_ref(xh, dtf, A, Bm2, Cm2, chunk)

    # --- cross-shard state prefix ---
    dA_cum = jnp.cumsum(dtf * A, axis=1)                 # [B, S, H]
    total_decay = jnp.exp(dA_cum[:, -1, :])              # [B, H]
    states = jax.lax.all_gather(state_loc, axis)         # [n, B, H, P, N]
    decays = jax.lax.all_gather(total_decay, axis)       # [n, B, H]
    prefix = jnp.zeros_like(state_loc)
    prefixes = [prefix]
    for j in range(n_shards - 1):
        prefix = prefix * decays[j][:, :, None, None] + states[j]
        prefixes.append(prefix)
    state_in = jnp.stack(prefixes)[idx]                  # [B, H, P, N]
    # fold the incoming state: y_t += C_t . (state_in * exp(dA_cum_t))
    y_corr = jnp.einsum("bsn,bhpn->bshp", Cm2.astype(jnp.float32),
                        state_in) * jnp.exp(dA_cum)[..., None]
    y = y_loc.astype(jnp.float32) + y_corr
    y = y + xh.astype(jnp.float32) * p["mamba"]["D"][:, None]
    y = y.reshape(Bsz, S, d_in).astype(x.dtype)
    y = L.rms_norm(y * jax.nn.silu(z), p["mamba"]["norm"], cfg.norm_eps)
    out = jnp.einsum("bsp,pd->bsd", y, p["mamba"]["out_proj"])
    return x + out


def seq_parallel_forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
                         mesh: Mesh, axis: str = "model") -> jax.Array:
    """Full mamba2 LM forward with the sequence sharded over ``axis``.

    Weights replicated over ``axis``; batch sharded over (pod, data) by the
    caller's in_shardings.  Returns last-position logits [B, V].
    """
    assert cfg.family == "ssm"

    def body(params, tokens):
        x = jnp.take(params["wte"], tokens, axis=0)

        def layer(x, lp):
            return _mamba_block_local(lp, x, cfg, axis), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x

    # shard_map over the model axis only; batch/data sharding is handled by
    # the outer pjit (the specs below say how ONE (data-)shard's slice is
    # split across the model axis).
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bspec = dp if (dp and tokens.shape[0] % dp_size == 0) else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(bspec, axis)),
        out_specs=P(bspec, axis, None),
        check_vma=False,
    )
    x = fn(params, tokens)
    logits = jnp.einsum("bd,dv->bv", x[:, -1, :], params["head"]) \
        if not cfg.tie_embeddings else \
        jnp.einsum("bd,vd->bv", x[:, -1, :], params["wte"])
    return logits
