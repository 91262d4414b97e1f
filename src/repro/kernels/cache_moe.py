"""Slot-indexed grouped MoE FFN over ExpertCache slot buffers.

The SP-MoE offload runtime keeps a fixed pool of expert-weight *slots* in
device memory (``core/cache.py``); routing produces, per (token, choice), a
**slot id** (via the device-side page table ``[L, E] -> slot | -1``) and a
combine weight.  This kernel computes

    y[t] = sum_c  w[t, c] * FFN_{slots[t, c]}(x[t])        (slots[t,c] >= 0)

entirely on device: tokens are capacity-gathered by slot into ``[S, C, d]``
and pushed through the same blocked gate/up/down Pallas stages as
``moe_gemm.py`` (the slot axis is the leading parallel grid dim), then
combined back with the masked weights.  Choices with ``slot < 0`` (cache
misses, or entries masked out of a compute wave) contribute exactly zero —
cached-first and miss-wave compute share this one fused path, differing only
in which slots are masked.

A slot receives at most one choice per token: a token's k experts are
distinct (``lax.top_k``) and the page table maps distinct experts to
distinct slots.  So the capacity per slot is ``T`` rows, rounded up to the
row block and never under 16 — no drops, which speculative-decoding
losslessness requires (``T·k`` rows would be six times as many at top-6).
Callers keep to that: a token names a slot at most once.  A block can
route to at most ``T·k`` *distinct* slots, so when the pool is larger than
that the slot axis is **occupancy-compacted** before the GEMM: the ≤
``min(S, T·k)`` slots that actually received a choice are renumbered
densely, only their weight rows are gathered (``compacted_slots``), and the
grid covers ``M = min(S, T·k)`` slots instead of ``S`` —
FLOPs and weight traffic are O(M·C·d·f), independent of the pool size.
(Previously the grid covered all S slots at capacity C, burning O(S·C·d·f)
on empty slots — ROADMAP open item, closed.)  Each row's blocked
accumulation is unchanged by the renumbering, so compaction is numerically
transparent.

Oracle: kernels/ref.cache_moe_ref (ragged grouping, same compaction idea).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.moe_gemm import (_down_kernel, _gate_up_kernel, block_f_for,
                                    moe_gemm)

MIN_CAPACITY = 16


def _capacity(rows: int, block_c: int) -> int:
    """Smallest valid per-slot capacity: >= ``rows``, the most choices one
    slot can receive (zero drops), and >= ``MIN_CAPACITY``, rounded so the
    blocked kernel's ``C % bc == 0`` constraint holds."""
    c = max(MIN_CAPACITY, -(-rows // 8) * 8)
    if c > block_c:
        c = -(-c // block_c) * block_c
    return c


def compacted_slots(tokens: int, k: int, num_slots: int) -> int:
    """How many slots ``cache_moe`` compacts a pool of ``num_slots`` to for
    ``tokens`` rows of k choices: M = min(S, T·k) when the pool is larger
    (its weight rows are gathered before the grid), else 0 (no gather)."""
    m = min(num_slots, tokens * k)
    return m if num_slots > m else 0


def compact_occupied_slots(slot_ids: jax.Array, wu: jax.Array, wd: jax.Array,
                           wg: Optional[jax.Array], num_compact: int
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      Optional[jax.Array]]:
    """Renumber the occupied slots densely into ``[0, num_compact)`` and
    gather just their weight rows.

    slot_ids: [T, k] int (-1 = skip) over a pool of ``S = wu.shape[0]``
    slots.  A block of T·k choices touches at most ``min(S, T·k)`` distinct
    slots, so ``num_compact`` that large is always drop-free.  Returns
    (compact_ids [T, k] in [0, num_compact) ∪ {-1}, wu_c, wd_c, wg_c with a
    leading axis of ``num_compact``).  Unoccupied compact rows keep slot 0's
    weights — harmless, no choice maps to them.
    """
    S = wu.shape[0]
    flat = slot_ids.reshape(-1)
    valid = flat >= 0
    # occupancy via add (a set-scatter would race -1-clipped misses against
    # real hits on slot 0 with differing values)
    counts = jnp.zeros((S,), jnp.int32).at[
        jnp.where(valid, flat, 0)].add(valid.astype(jnp.int32))
    occ = counts > 0
    rank = jnp.cumsum(occ.astype(jnp.int32)) - 1          # dense renumbering
    inv = jnp.where(occ, rank, -1)                        # slot -> compact
    comp2slot = jnp.zeros((num_compact,), jnp.int32).at[
        jnp.where(occ, rank, num_compact)].set(
        jnp.arange(S, dtype=jnp.int32), mode="drop")
    comp_ids = jnp.where(slot_ids >= 0,
                         inv[jnp.clip(slot_ids, 0, S - 1)], -1)
    take = lambda w: None if w is None else jnp.take(w, comp2slot, axis=0)
    return comp_ids, take(wu), take(wd), take(wg)


def dispatch_to_slots(slot_ids: jax.Array, num_slots: int, capacity: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """slot_ids: [T, k] int (-1 = skip) ->
    (idx [S, C] token index per capacity slot, valid [S, C],
    pos [T, k] capacity position each choice landed in; C for skipped).

    Same sorted-rank construction as models/moe._dispatch_indices, but over
    cache slots instead of experts and with a skip lane for negative ids.
    """
    T, k = slot_ids.shape
    flat = slot_ids.reshape(-1)
    sane = jnp.where(flat >= 0, flat, num_slots)          # skips -> overflow row
    order = jnp.argsort(sane, stable=True)
    sorted_s = sane[order]
    starts = jnp.searchsorted(sorted_s, jnp.arange(num_slots))
    rank = jnp.arange(T * k) - starts[sorted_s]
    tok = (order // k).astype(jnp.int32)
    idx = jnp.zeros((num_slots, capacity), jnp.int32).at[
        sorted_s, rank].set(tok, mode="drop")
    valid = jnp.zeros((num_slots, capacity), jnp.bool_).at[
        sorted_s, rank].set(True, mode="drop")
    in_range = (rank < capacity) & (sorted_s < num_slots)
    pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(in_range, rank, capacity).astype(jnp.int32))
    return idx, valid, pos.reshape(T, k)


# --------------------------------------------------------------------------
# gelu stage-1 (single up-projection) — the swiglu stage lives in moe_gemm.py
# --------------------------------------------------------------------------

def _up_gelu_kernel(x_ref, wu_ref, h_ref, acc_ref):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], wu_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _fin():
        h_ref[0] = jax.nn.gelu(acc_ref[...]).astype(h_ref.dtype)


def _gelu_grouped(xg: jax.Array, wu: jax.Array, wd: jax.Array,
                  valid: jax.Array, *, block_c: int, block_f: Optional[int],
                  block_d: int, interpret: bool) -> jax.Array:
    S, C, d = xg.shape
    f = wu.shape[2]
    bc, bf, bd = min(block_c, C), block_f_for(f, block_f), min(block_d, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0
    h = pl.pallas_call(
        _up_gelu_kernel,
        grid=(S, C // bc, f // bf, d // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((S, C, f), xg.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xg, wu)
    y = pl.pallas_call(
        _down_kernel,
        grid=(S, C // bc, d // bd, f // bf),
        in_specs=[
            pl.BlockSpec((1, bc, bf), lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bf, bd), lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd), lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((S, C, d), xg.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(h, wd)
    return y * valid[..., None]


def cache_moe(x: jax.Array, slot_ids: jax.Array, weights: jax.Array,
              wu: jax.Array, wd: jax.Array, wg: Optional[jax.Array] = None,
              *, block_c: int = 128, block_f: Optional[int] = None,
              block_d: int = 512, interpret: bool = False) -> jax.Array:
    """x: [T, d]; slot_ids/weights: [T, k]; wu/wg: [S, d, f]; wd: [S, f, d]
    -> y [T, d].  slot_ids < 0 (miss / masked-out wave) contribute zero; a
    token names each slot at most once."""
    T, d = x.shape
    k = slot_ids.shape[1]
    S = wu.shape[0]
    C = _capacity(T, block_c)
    M = compacted_slots(T, k, S)
    if M:              # occupancy compaction: grid covers M slots, not S
        slot_ids, wu, wd, wg = compact_occupied_slots(slot_ids, wu, wd, wg, M)
        S = M
    idx, valid, pos = dispatch_to_slots(slot_ids, S, C)
    xg = jnp.take(x, idx.reshape(-1), axis=0).reshape(S, C, d)
    if wg is not None:
        yg = moe_gemm(xg, wg, wu, wd, valid, block_c=block_c,
                      block_f=block_f, block_d=block_d, interpret=interpret)
    else:
        yg = _gelu_grouped(xg, wu, wd, valid, block_c=block_c,
                           block_f=block_f, block_d=block_d,
                           interpret=interpret)
    # combine: read each (token, choice)'s row back from (slot, pos); pos == C
    # lands in the zero-padded lane so skipped choices vanish.
    ygp = jnp.pad(yg, ((0, 0), (0, 1), (0, 0)))
    flat = ygp.reshape(S * (C + 1), d)
    safe = jnp.where(slot_ids >= 0, slot_ids, 0)
    gidx = (safe * (C + 1) + pos).reshape(-1)
    per = jnp.take(flat, gidx, axis=0).reshape(T, k, d)
    w = jnp.where(slot_ids >= 0, weights, 0.0).astype(jnp.float32)
    y = jnp.sum(per.astype(jnp.float32) * w[..., None], axis=1)
    return y.astype(x.dtype)
