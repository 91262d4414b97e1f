"""Grouped expert FFN (MoE GEMM) Pallas TPU kernels.

Operates on capacity-gathered tokens ``xg [E, C, d]`` against per-expert
weights (the hot loop of both routing paths in models/moe.py and of the
SP-MoE offload runtime's cached-expert compute).  Two fused stages:

  stage 1   h = silu(x @ wg) * (x @ wu)     (gate+up fused, one pass over x)
  stage 2   y = h @ wd

Both are blocked [bc × bk × bn] with f32 VMEM accumulators; the expert axis
is the leading parallel grid dim, so on an EP-sharded mesh each core runs its
local experts only.

Oracle: kernels/ref.moe_gemm_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gate_up_kernel(x_ref, wg_ref, wu_ref, h_ref, accg_ref, accu_ref):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[0]
    accg_ref[...] += jax.lax.dot_general(
        x, wg_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot_general(
        x, wu_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _fin():
        h_ref[0] = (jax.nn.silu(accg_ref[...]) * accu_ref[...]).astype(h_ref.dtype)


def _down_kernel(h_ref, wd_ref, y_ref, acc_ref):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        h_ref[0], wd_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _fin():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


# An expert at most this wide is one block along f: at block_c 128 and
# block_d 512 its double-buffered gate/up tiles and f32 accumulators take
# about 8 MB of VMEM (DeepSeek-V2-Lite's 1408 = 11 x 128, whose only
# lane-aligned blocks are 128 and 1408).  Wider experts take the largest
# lane-aligned block up to BLOCK_F that divides them.
FULL_F = 2048
BLOCK_F = 512


def block_f_for(f: int, block_f: Optional[int] = None) -> int:
    """The f block: ``block_f`` when given (it must divide f), else f whole
    up to ``FULL_F``, else the largest multiple of 128 up to ``BLOCK_F``
    that divides f."""
    if block_f is None:
        block_f = f if f <= FULL_F else max(
            (b for b in range(128, BLOCK_F + 1, 128) if f % b == 0),
            default=BLOCK_F)
    bf = min(block_f, f)
    if f % bf:
        raise ValueError(f"block_f {bf} does not divide the expert width {f}")
    return bf


def moe_gemm(xg: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
             valid: jax.Array, *, block_c: int = 128,
             block_f: Optional[int] = None, block_d: int = 512,
             interpret: bool = False) -> jax.Array:
    """xg: [E,C,d]; wg/wu: [E,d,f]; wd: [E,f,d]; valid: [E,C] -> [E,C,d]."""
    E, C, d = xg.shape
    f = wg.shape[2]
    bc = min(block_c, C)
    bf = block_f_for(f, block_f)
    bd = min(block_d, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0

    h = pl.pallas_call(
        _gate_up_kernel,
        grid=(E, C // bc, f // bf, d // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), xg.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32),
                        pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xg, wg, wu)

    y = pl.pallas_call(
        _down_kernel,
        grid=(E, C // bc, d // bd, f // bf),
        in_specs=[
            pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bf, bd), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), xg.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(h, wd)
    return y * valid[..., None]
