"""Fine-grained experts in the serving kernel and the router: ``cache_moe``
at DeepSeek-V2-Lite's expert width (1408 = 11 x 128) and at a small width
that is no multiple of any block, each against ``ref.cache_moe_ref``; the
per-slot capacity from the token count, with no choice dropped at that
bound; the expert-width blocks; the compaction rule the runtime counts; and
``gate_topk`` with and without renormalisation (Mixtral's output is what it
was)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import ref as R
from repro.kernels.cache_moe import (_capacity, cache_moe, compacted_slots,
                                     dispatch_to_slots)
from repro.kernels.moe_gemm import block_f_for
from repro.models.moe import gate_topk


def _inputs(T, k, S, d, f, seed, slot_ids):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (S, d, f)) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (S, d, f)) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (S, f, d)) / np.sqrt(f)
    w = jax.random.uniform(ks[4], (T, k))
    return x, wg, wu, wd, jnp.asarray(slot_ids, jnp.int32), w


def _distinct_slots(T, k, S, seed, miss=0.2):
    """Each token's k choices name distinct slots (distinct experts through
    an injective page table); about ``miss`` of them are misses (-1)."""
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.permutation(S)[:k] for _ in range(T)])
    return np.where(rng.random((T, k)) < miss, -1, ids)


@pytest.mark.parametrize("T,S,f", [
    (5, 40, 1408),       # a decode block, compacted from 40 slots to 30
    (24, 12, 1408),      # more rows than the 16-row floor, no compaction
    (5, 40, 200),        # a small width that is no multiple of a block
])
def test_cache_moe_at_fine_grained_widths_matches_the_oracle(T, S, f):
    k, d = 6, 128
    x, wg, wu, wd, si, w = _inputs(T, k, S, d, f, 7,
                                   _distinct_slots(T, k, S, 3))
    out = cache_moe(x, si, w, wu, wd, wg, interpret=True)
    want = R.cache_moe_ref(x, si, w, wu, wd, wg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_no_choice_is_dropped_at_the_capacity_of_t_rows():
    """Every token routes to the same k slots, so each of them receives
    one choice from every token: T rows, the most a slot can get."""
    T, k, S = 40, 6, 64
    slot_ids = np.tile(np.arange(k), (T, 1))
    C = _capacity(T, 128)
    assert C == T
    idx, valid, pos = dispatch_to_slots(jnp.asarray(slot_ids), S, C)
    assert int(np.asarray(valid).sum()) == T * k
    assert (np.asarray(pos) < C).all()
    x, wg, wu, wd, si, w = _inputs(T, k, S, 16, 24, 2, slot_ids)
    np.testing.assert_allclose(
        np.asarray(cache_moe(x, si, w, wu, wd, wg, interpret=True)),
        np.asarray(R.cache_moe_ref(x, si, w, wu, wd, wg)),
        atol=1e-5, rtol=1e-4)


def test_capacity_comes_from_tokens_with_a_floor_of_16_rows():
    # Mixtral's decode block (T=5, top-2) keeps its [7, 16, .] shape
    assert _capacity(5, 128) == 16
    # prefill blocks: T rows, no longer T*k
    assert [_capacity(T, 128) for T in (128, 256, 512)] == [128, 256, 512]
    assert _capacity(20, 128) == 24 and _capacity(130, 128) == 256


def test_compaction_rule():
    # DeepSeek-V2-Lite's decode block over a 128-slot pool: 30 slots
    assert compacted_slots(5, 6, 128) == 30
    # no gather when the pool is no larger than a block's choices
    assert compacted_slots(5, 2, 7) == 0
    assert compacted_slots(512, 6, 128) == 0


def test_expert_width_blocks():
    assert block_f_for(14336) == 512          # Mixtral-8x7B, as before
    assert block_f_for(16384) == 512          # Mixtral-8x22B
    assert block_f_for(1408) == 1408          # DeepSeek-V2-Lite: whole
    assert block_f_for(6400) == 256           # Phi-3.5-MoE
    assert block_f_for(1408, 128) == 128
    with pytest.raises(ValueError):
        block_f_for(1408, 512)


def test_mixtral_gate_topk_is_unchanged():
    """The renormalised softmax top-k, computed as it always was."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    gate = jax.random.normal(ks[0], (cfg.d_model, cfg.num_experts))
    x = jax.random.normal(ks[1], (6, cfg.d_model))
    w, ids, probs, _ = gate_topk(gate, x, 2, cfg.norm_topk_prob)
    p = jax.nn.softmax(jnp.einsum("td,de->te", x, gate), -1)
    top, top_ids = jax.lax.top_k(p, 2)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(top_ids))
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(top / top.sum(-1, keepdims=True)))


def test_deepseek_gate_keeps_raw_probabilities():
    cfg = get_config("deepseek-v2-lite-16b").reduced(
        dtype="float32", num_experts=64, num_experts_per_tok=6)
    assert cfg.norm_topk_prob is False
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    gate = jax.random.normal(ks[0], (cfg.d_model, 64))
    x = jax.random.normal(ks[1], (6, cfg.d_model))
    w, ids, probs, _ = gate_topk(gate, x, 6, cfg.norm_topk_prob)
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(probs),
                                          np.asarray(ids), -1), rtol=1e-6)
    assert (np.asarray(w).sum(-1) < 1).all()
