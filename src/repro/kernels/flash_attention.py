"""Flash attention (prefill) Pallas TPU kernel.

Blocked online-softmax attention with causal + sliding-window masking and
GQA head folding.  Tiling is MXU/VMEM-oriented: q blocks × kv blocks, f32
accumulators in VMEM scratch, one (head, q-block) owns its accumulator across
the sequential kv-block grid axis.

Oracle: kernels/ref.attention_ref.  Validated in interpret mode
(tests/test_kernels.py); on a real TPU the same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, sq: int, skv: int, causal: bool,
                  window: Optional[int], scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [bq, D]
    k = k_ref[0]                                   # [bk, D]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # absolute positions (queries are right-aligned to the kv sequence)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)                # [bq, 1]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Sq, H, D]; k/v: [B, Skv, Hkv, D] -> [B, Sq, H, D].

    Heads fold into the grid's leading (parallel) axis; GQA maps q-head h to
    kv-head h // (H // Hkv) in the k/v index maps.
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, "pad sequences to block multiples"
    # layout: [B*H, S, D] so a grid step owns one (head, q-block)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, sq=Sq, skv=Skv, causal=causal,
        window=window, scale=1.0 / np.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, Skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, rep=rep, Hkv=Hkv:
                         ((h // rep) % Hkv + (h // (rep * Hkv)) * Hkv, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, rep=rep, Hkv=Hkv:
                         ((h // rep) % Hkv + (h // (rep * Hkv)) * Hkv, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
