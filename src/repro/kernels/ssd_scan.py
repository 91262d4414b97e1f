"""Mamba2 SSD (state-space dual) chunked-scan Pallas TPU kernel.

Grid: (batch, heads, chunks) with the chunk axis sequential — the inter-chunk
SSM state [P, N] lives in VMEM scratch and is carried across grid steps
(TPU "arbitrary" dimension semantics guarantee in-order execution).

Per chunk (length Q):
  intra  Y  = (C B^T ∘ L) · (dt ⊙ X)        L = exp(segsum(dt·A)) causal
  carry  S' = S·exp(sum dA) + (B·decay)^T (dt ⊙ X)
  inter  Y += C S · exp(cumsum dA)

Oracle: kernels/ref.ssd_ref (single B/C group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # [Q, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # [Q, 1] (padded lane dim)
    a_scalar = a_ref[0]                           # this head's A (scalar)
    B = b_ref[0, 0].astype(jnp.float32)           # [Q, N]
    C = c_ref[0, 0].astype(jnp.float32)           # [Q, N]
    dtv = dt[:, 0]                                # [Q]
    dA = dtv * a_scalar                           # [Q]
    dA_cum = jnp.cumsum(dA)                       # inclusive
    # intra-chunk
    seg = dA_cum[:, None] - dA_cum[None, :]       # [Q, Q]
    qidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(kidx <= qidx, jnp.exp(seg), 0.0)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    xdt = x * dtv[:, None]                        # [Q, P]
    y = jax.lax.dot_general(CB * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk (uses state BEFORE this chunk)
    state = state_ref[...]                        # [N, P]
    y += jnp.exp(dA_cum)[:, None] * jax.lax.dot_general(
        C, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # carry state
    decay_to_end = jnp.exp(dA_cum[-1] - dA_cum)   # [Q]
    state_ref[...] = state * jnp.exp(dA_cum[-1]) + jax.lax.dot_general(
        B * decay_to_end[:, None], xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # [N, P]
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int, *, interpret: bool = False) -> jax.Array:
    """x: [b,s,h,p]; dt: [b,s,h]; A: [h]; B,C: [b,s,n] -> y [b,s,h,p].

    (Final state is not returned by the kernel path — training/prefill uses
    ssd_ref when the state is needed; see models/mamba.py.)
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    xb = x.transpose(0, 2, 1, 3).reshape(b, h, nc, chunk, p)
    dtb = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk, 1)
    Bb = B.reshape(b, nc, chunk, n)
    Cb = C.reshape(b, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p), lambda i, j, c: (i, j, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda i, j, c: (i, j, c, 0, 0)),
            pl.BlockSpec((1,), lambda i, j, c: (j,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p), lambda i, j, c: (i, j, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xb, dtb, A.astype(jnp.float32), Bb, Cb)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
