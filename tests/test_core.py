"""SP-MoE core: LRU cache invariants (property), cutoff solver, prefetcher
pipeline, offload engine losslessness + prefetch accounting."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import make_draft_for
from repro.configs.registry import get_config
from repro.core.cache import ExpertCache
from repro.core.cutoff import HardwareProfile, solve_cutoff
from repro.core.engine import Engine, EngineConfig, Request
from repro.core.offload import HostExpertStore
from repro.core.prefetcher import Prefetcher
from repro.core.predictor import ExpertPredictor, strategy_entropies
from repro.core.sd import greedy_generate


# ---------------------------------------------------------------------------
# LRU expert cache
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["insert", "lookup"]),
                          st.integers(0, 5), st.integers(0, 7)),
                min_size=1, max_size=60),
       st.integers(1, 6))
def test_cache_invariants_under_op_sequences(ops, slots):
    """Any interleaving of inserts/lookups keeps the page table consistent:
    no slot aliasing, table==lru keys, free+used==capacity."""
    cache = ExpertCache(slots, {"w": (2, 2)}, jnp.float32)
    arrays = {"w": np.ones((1, 2, 2), np.float32)}
    for op, layer, expert in ops:
        key = (layer, expert)
        if op == "insert":
            cache.insert([key], arrays)
        else:
            cache.lookup([key])
        assert cache.check_invariants()
    assert len(cache.table) <= slots


def test_cache_lru_eviction_order():
    cache = ExpertCache(2, {"w": (1,)}, jnp.float32)
    a = {"w": np.zeros((1, 1), np.float32)}
    cache.insert([(0, 0)], a)
    cache.insert([(0, 1)], a)
    cache.lookup([(0, 0)])             # touch 0 -> 1 becomes LRU victim
    cache.insert([(0, 2)], a)
    assert cache.contains((0, 0))
    assert not cache.contains((0, 1))
    assert cache.contains((0, 2))
    assert cache.check_invariants()


def test_cache_batched_insert_contents():
    cache = ExpertCache(4, {"w": (2,)}, jnp.float32)
    arrays = {"w": np.stack([np.full((2,), i, np.float32) for i in range(3)])}
    slots = cache.insert([(0, 0), (0, 1), (0, 2)], arrays)
    bufs = np.asarray(cache.bufs["w"])
    for i, s in enumerate(slots):
        np.testing.assert_array_equal(bufs[s], np.full((2,), i))


def test_cache_insert_survives_the_staging_buffer_being_refilled():
    """The caller's staging memory is refilled (HostExpertStore.fetch reuses
    it two fetches later) while the insert's scatter still waits behind
    earlier device work: the slot holds what was inserted.  On the CPU a
    device_put of an aligned numpy array aliases it, so without a copy the
    slot read the refilled bytes."""
    cache = ExpertCache(4, {"w": (256, 256)}, jnp.float32)

    @jax.jit
    def slow(b):
        for _ in range(60):
            b = jnp.tanh(b @ b) * 0 + b
        return b

    raw = np.zeros(16 * 256 * 256 + 16, np.float32)
    off = (-raw.ctypes.data % 64) // 4          # 64-byte aligned, as staged
    staged = raw[off:off + 16 * 256 * 256].reshape(16, 256, 256)
    staged[:1] = 1.0
    cache.bufs = {n: slow(b) for n, b in cache.bufs.items()}
    cache.insert([(0, 0)], {"w": staged[:1]})
    staged[:1] = 2.0
    slot = np.asarray(cache.bufs["w"][cache.table[(0, 0)]])
    np.testing.assert_array_equal(slot, np.ones((256, 256), np.float32))


# ---------------------------------------------------------------------------
# cutoff solver (paper §3.2)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.floats(1e-4, 1e-2), st.floats(1e-4, 1e-2), st.floats(1e-3, 3e-2),
       st.integers(4, 48), st.integers(1, 6), st.integers(1, 8),
       st.floats(1e9, 40e9))
def test_cutoff_satisfies_constraints(t_comp, t_draft, t_io, layers, k,
                                      draft_len, mem_gpu):
    prof = HardwareProfile(t_comp=t_comp, t_comp_draft=t_draft, t_io=t_io,
                           mem_gpu=mem_gpu, mem_peak=mem_gpu * 0.3,
                           mem_expert=300e6)
    dec = solve_cutoff(prof, k, layers, draft_len)
    L = dec.cutoff_layer
    assert -1 <= L < layers
    if L >= 0:
        n = (L + 1) * k
        # memory constraint
        assert prof.mem_peak + n * prof.mem_expert < prof.mem_gpu
        # overlap constraint (paper's inequality)
        budget = layers * t_draft * draft_len
        assert max((L - 1) * t_draft + k * t_io, n * t_io) <= budget + 1e-12
    if L + 1 < layers:
        # maximality: L+1 must violate one constraint
        n2 = (L + 2) * k
        budget = layers * t_draft * draft_len
        mem_bad = prof.mem_peak + n2 * prof.mem_expert >= prof.mem_gpu
        ovl_bad = max(L * t_draft + k * t_io, n2 * t_io) > budget
        assert mem_bad or ovl_bad


# ---------------------------------------------------------------------------
# prefetcher pipeline
# ---------------------------------------------------------------------------

def _toy_engine(policy="spmoe", slots=6):
    """Unified-API engine (core/engine.py); eng.runtime is the offload
    layer underneath."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    dcfg = make_draft_for(cfg)
    from repro.models.registry import build_model
    target = build_model(cfg)
    draft = build_model(dcfg)
    tparams = target.init(jax.random.PRNGKey(0))
    dparams = draft.init(jax.random.PRNGKey(1))
    eng = Engine(EngineConfig(model=cfg, draft=dcfg, decode="sd",
                              offload=policy, cache_slots=slots,
                              draft_len=3, max_seq=48), tparams, dparams)
    return cfg, target, tparams, eng


def test_prefetch_worker_loads_async():
    cfg, target, tparams, eng = _toy_engine()
    rt = eng.runtime
    keys = [(0, 0), (0, 1), (1, 2)]
    task = rt.prefetcher.submit(keys)
    task.done.wait(timeout=10)
    assert all(rt.cache.contains(k) for k in keys)
    assert rt.prefetcher.loaded_count == 3
    assert rt.prefetcher.io_events == [3]       # batched: one transfer
    eng.close()


def test_prefetcher_unbatched_issues_per_expert():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    from repro.models.registry import build_model
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    cache = ExpertCache(8, store.buffer_shapes(), jnp.float32)
    pf = Prefetcher(store, cache, mode="worker", batched=False)
    task = pf.submit([(0, 0), (1, 1), (2, 2)])
    task.done.wait(timeout=10)
    assert pf.io_events == [1, 1, 1]
    pf.stop()


@pytest.mark.parametrize("policy", ["spmoe", "on-demand"])
def test_offload_engine_lossless(policy):
    cfg, target, tparams, eng = _toy_engine(policy)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_size)
    ref = greedy_generate(target, tparams, prompt, 14, 48)
    res = eng.submit(Request(prompt=prompt, max_new_tokens=14))
    out, stats = res.token_array(), res.metrics
    eng.close()
    assert out.tolist() == ref.tolist()
    if policy == "spmoe":
        assert stats["prefetched"] > 0
    else:
        assert stats["prefetched"] == 0
        assert stats["on_demand_loads"] > 0


def test_spmoe_prefetch_improves_hit_rate():
    _, _, _, e1 = _toy_engine("on-demand", slots=10)
    cfg, _, _, e2 = _toy_engine("spmoe", slots=10)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_size)
    s1 = e1.submit(Request(prompt=prompt, max_new_tokens=12)).metrics
    s2 = e2.submit(Request(prompt=prompt, max_new_tokens=12)).metrics
    e1.close()
    e2.close()
    assert s2["hit_rate"] >= s1["hit_rate"]


# ---------------------------------------------------------------------------
# predictor analytics (Observation I)
# ---------------------------------------------------------------------------

def test_strategy_entropies_ordering():
    """Gating-based prediction must be lower-entropy than random; Fig 2c."""
    rng = np.random.default_rng(0)
    E, T = 8, 64
    logits = rng.normal(size=(T, E)) * 3.0        # skewed per-token gates
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    hist = rng.integers(1, 100, size=E).astype(float)
    ent = strategy_entropies(probs, hist)
    assert ent["gating_based"] < ent["random"]
    assert ent["coarse_grained"] <= ent["random"] + 1e-6


def test_predictor_matches_gate_topk():
    cfg, target, tparams, eng = _toy_engine()
    pred = eng.runtime.predictor
    tap = jax.random.normal(jax.random.PRNGKey(3), (1, 1, cfg.d_model))
    keys = pred.predict_layer(0, tap, np.asarray)
    # manual: top-k of softmax(tap @ gate_0)
    gate = np.asarray(tparams["layers"]["moe"]["gate"])[0]
    scores = np.asarray(tap).reshape(-1) @ gate
    top = set(np.argsort(-scores)[: pred.k].tolist())
    assert {e for (_, e) in keys} == top
    eng.close()
