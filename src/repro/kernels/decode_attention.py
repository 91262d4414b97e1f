"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Grid: (batch × kv-head, kv blocks).  The q block is the [rep, D] group of
query heads sharing one kv head (GQA), so the MXU sees a [rep, D] x [D, bk]
matmul per step.  Online softmax across kv blocks; valid-length masking from
a per-batch length vector (SMEM).

Oracle: kernels/ref.decode_attention_ref.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk: int, scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [rep, D]
    k = k_ref[0]                                   # [bk, D]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    length = len_ref[0]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, block_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k/v: [B, S, Hkv, D]; lengths: [B] -> [B, H, D]."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    bk = min(block_k, S)
    assert S % bk == 0, "pad the KV cache to a block multiple"
    qf = q.reshape(B * Hkv, rep, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), Hkv)       # [B*Hkv]

    kernel = functools.partial(_decode_kernel, bk=bk, scale=1.0 / np.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda h, j: (h,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rep, D), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, D), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, rep, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, H, D)
