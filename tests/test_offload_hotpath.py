"""Device-resident verification hot path: slot-indexed kernel parity, device
page-table consistency (incl. under concurrent prefetch), the ≤2-host-syncs
contract of the fast verify path, prefetcher drain correctness, and the
HostExpertStore staging/strip_experts regressions."""
import dataclasses
import threading
import time
import tracemalloc

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from conftest import make_draft_for
from repro.configs.registry import get_config
from repro.core.cache import ExpertCache
from repro.core.engine import Engine, EngineConfig, Request
from repro.core.offload import HostExpertStore
from repro.core.prefetcher import Prefetcher
from repro.core.sd import greedy_generate
from repro.kernels import ref as R
from repro.kernels.cache_moe import _capacity, cache_moe, dispatch_to_slots
from repro.models.registry import build_model


# ---------------------------------------------------------------------------
# slot-indexed cache MoE kernel vs oracle
# ---------------------------------------------------------------------------

def _tol(dtype):
    return 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 2e-5


@pytest.mark.slow
@pytest.mark.parametrize("T,k,S,d,f", [
    (5, 2, 6, 32, 64),        # verify-block shaped
    (1, 2, 4, 16, 32),        # single token
    (8, 4, 16, 64, 32),       # wider top-k
    (16, 1, 3, 32, 32),       # k=1, few slots
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cache_moe_parity_swiglu(T, k, S, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (T, d), dtype)
    wg = (jax.random.normal(ks[1], (S, d, f)) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (S, d, f)) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (S, f, d)) * 0.1).astype(dtype)
    slot_ids = jax.random.randint(ks[4], (T, k), -1, S)   # includes misses
    weights = jax.random.uniform(ks[5], (T, k), dtype)
    out = cache_moe(x, slot_ids, weights, wu, wd, wg, interpret=True)
    ref = R.cache_moe_ref(x, slot_ids, weights, wu, wd, wg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=2e-2)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cache_moe_parity_gelu(dtype):
    """No-wg (gelu up-projection) variant."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    T, k, S, d, f = 6, 2, 5, 32, 64
    x = jax.random.normal(ks[0], (T, d), dtype)
    wu = (jax.random.normal(ks[1], (S, d, f)) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[2], (S, f, d)) * 0.1).astype(dtype)
    slot_ids = jax.random.randint(ks[3], (T, k), -1, S)
    weights = jax.random.uniform(ks[4], (T, k), dtype)
    out = cache_moe(x, slot_ids, weights, wu, wd, None, interpret=True)
    ref = R.cache_moe_ref(x, slot_ids, weights, wu, wd, None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=2e-2)


@pytest.mark.slow
def test_cache_moe_masked_and_zero_weight_choices():
    """slot < 0 and weight == 0 choices contribute exactly zero; duplicate
    slots for one token accumulate."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    T, k, S, d, f = 5, 2, 6, 32, 32
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (S, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (S, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (S, f, d)) * 0.1
    weights = jax.random.uniform(ks[4], (T, k))
    # all masked -> exact zero
    all_miss = jnp.full((T, k), -1, jnp.int32)
    out = cache_moe(x, all_miss, weights, wu, wd, wg, interpret=True)
    assert bool(jnp.all(out == 0))
    # zero weight kills the choice even when the slot is valid
    si = jnp.array([[0, 0], [5, 5], [2, 3], [1, -1], [4, 2]], jnp.int32)
    w0 = weights.at[:, 1].set(0.0)
    out = cache_moe(x, si, w0, wu, wd, wg, interpret=True)
    only_first = cache_moe(x, si.at[:, 1].set(-1), weights, wu, wd, wg,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(only_first),
                               atol=1e-5, rtol=1e-4)
    # duplicate-slot parity vs oracle
    ref = R.cache_moe_ref(x, si, weights, wu, wd, wg)
    out = cache_moe(x, si, weights, wu, wd, wg, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)


def test_cache_moe_ref_matches_dense_loop():
    """The oracle itself vs a naive per-choice python loop."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    T, k, S, d, f = 4, 3, 5, 16, 24
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    wg = jax.random.normal(ks[1], (S, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (S, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (S, f, d)) * 0.1
    slot_ids = np.asarray(jax.random.randint(ks[4], (T, k), -1, S))
    weights = np.asarray(jax.random.uniform(ks[5], (T, k)))
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for c in range(k):
            s = int(slot_ids[t, c])
            if s < 0:
                continue
            h = jax.nn.silu(x[t] @ wg[s]) * (x[t] @ wu[s])
            want[t] += weights[t, c] * np.asarray(h @ wd[s])
    got = R.cache_moe_ref(x, jnp.asarray(slot_ids), jnp.asarray(weights),
                          wu, wd, wg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-3)


def test_dispatch_to_slots_no_drops():
    """Capacity is sized to the worst case — every valid choice lands."""
    T, k, S = 7, 2, 4
    C = _capacity(T * k, 128)
    rng = np.random.default_rng(0)
    slot_ids = jnp.asarray(rng.integers(-1, S, size=(T, k)), jnp.int32)
    idx, valid, pos = dispatch_to_slots(slot_ids, S, C)
    n_valid = int((np.asarray(slot_ids) >= 0).sum())
    assert int(np.asarray(valid).sum()) == n_valid
    posn = np.asarray(pos)
    assert ((posn < C) == (np.asarray(slot_ids) >= 0)).all()


# ---------------------------------------------------------------------------
# device page-table mirror
# ---------------------------------------------------------------------------

def _mk_cache(slots=4, L=3, E=5):
    cache = ExpertCache(slots, {"w": (2, 2)}, jnp.float32, table_shape=(L, E))
    arrays = {"w": np.ones((1, 2, 2), np.float32)}
    return cache, arrays, L, E


def test_table_array_tracks_inserts_and_evictions():
    cache, arrays, L, E = _mk_cache()
    rng = np.random.default_rng(0)
    for _ in range(200):
        key = (int(rng.integers(L)), int(rng.integers(E)))
        if rng.random() < 0.5:
            cache.insert([key], arrays)
        else:
            cache.lookup([key])
        assert cache.check_invariants()   # includes table_dev == table
    tdev = np.asarray(cache.table_dev)
    for (l, e), s in cache.table.items():
        assert tdev[l, e] == s
    assert (tdev >= 0).sum() == len(cache.table)


def test_table_scatter_trace_count_bounded():
    """Regression (ROADMAP open item): the jitted page-table scatter used to
    retrace per distinct scatter length, so trace-cache growth scaled with
    the number of distinct insert+eviction sizes.  Lengths are now padded to
    powers of two — many distinct sizes may compile at most one executable
    per bucket — and the padding (a repeated final triple) must keep the
    device mirror exact."""
    cache, _, L, E = _mk_cache(slots=8, L=6, E=9)
    rng = np.random.default_rng(0)
    seen_lengths = set()
    for _ in range(60):
        n = int(rng.integers(1, 7))
        keys = [(int(rng.integers(L)), int(rng.integers(E)))
                for _ in range(n)]
        arrays = {"w": np.ones((len(keys), 2, 2), np.float32)}
        cache.insert(keys, arrays)
        seen_lengths.add(n)
        assert cache.check_invariants()    # padding kept table_dev exact
    assert len(seen_lengths) >= 5, "sweep failed to vary insert sizes"
    # evictions extend scatter lengths further; buckets {1,2,4,8,16} bound
    # the executables regardless
    assert cache.table_scatter_traces <= 5, \
        f"scatter retraced per length: {cache.table_scatter_traces} traces"


def test_slot_scatter_compiles_one_executable_per_power_of_two():
    """A fine-grained model's load waves and prefetch batches take many
    sizes (top-6 of 64 experts: 1 to 30 fresh keys a layer), and a scatter
    compiled per size compiled inside the serving window.  The insert now
    scatters one power-of-two chunk per set bit of the batch size: 30
    distinct sizes compile at most 5 executables, and every slot holds the
    row inserted for it."""
    cache = ExpertCache(32, {"w": (3,)}, jnp.float32, table_shape=(1, 64))
    for n in range(1, 31):
        cache.free = list(range(32))          # start each batch empty
        cache.table.clear()
        cache.lru.clear()
        cache.table_dev = jnp.full((1, 64), -1, jnp.int32)
        keys = [(0, e) for e in range(n)]
        rows = np.stack([np.full((3,), 100 * n + i, np.float32)
                         for i in range(n)])
        slots = cache.insert(keys, {"w": rows})
        bufs = np.asarray(cache.bufs["w"])
        for i, s in enumerate(slots):
            np.testing.assert_array_equal(bufs[s], rows[i])
        assert cache.check_invariants()
    assert cache.insert_traces == 5, \
        f"slot scatter compiled per size: {cache.insert_traces} traces"


def test_table_array_consistent_under_concurrent_prefetch():
    """Prefetch worker + compute loop hammer the cache concurrently; the
    invariants (incl. the device table mirror) must hold throughout."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    L, E = store.num_layers, store.num_experts
    cache = ExpertCache(6, store.buffer_shapes(), jnp.float32,
                        table_shape=(L, E))
    pf = Prefetcher(store, cache, mode="worker", batched=True)
    stop = threading.Event()
    errs = []

    def compute_loop():
        rng = np.random.default_rng(1)
        try:
            while not stop.is_set():
                keys = [(int(rng.integers(L)), int(rng.integers(E)))
                        for _ in range(3)]
                hits, misses = cache.lookup(keys)
                if misses:
                    cache.insert(misses, store.fetch(misses), mark_used=True)
                with cache.lock:
                    assert cache.check_invariants()
        except Exception as e:  # surface across the thread boundary
            errs.append(e)

    t = threading.Thread(target=compute_loop)
    t.start()
    rng = np.random.default_rng(2)
    for _ in range(60):
        keys = [(int(rng.integers(L)), int(rng.integers(E)))
                for _ in range(4)]
        pf.submit(keys)
    pf.drain()
    stop.set()
    t.join(timeout=30)
    pf.stop()
    assert not errs, errs
    assert not pf.errors, pf.errors
    assert cache.check_invariants()


# ---------------------------------------------------------------------------
# ≤2 host syncs per verify block (fast path) + losslessness
# ---------------------------------------------------------------------------

def _toy_engine(policy="spmoe", slots=6, draft_len=3, precompile=True):
    """Unified-API engine; ``eng.runtime`` is the OffloadEngine underneath
    (the hot-path internals these tests spy on)."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    dcfg = make_draft_for(cfg)
    target = build_model(cfg)
    draft = build_model(dcfg)
    tparams = target.init(jax.random.PRNGKey(0))
    dparams = draft.init(jax.random.PRNGKey(1))
    eng = Engine(EngineConfig(model=cfg, draft=dcfg, decode="sd",
                              offload=policy, cache_slots=slots,
                              draft_len=draft_len, max_seq=64,
                              precompile=precompile),
                 tparams, dparams)
    return cfg, target, tparams, eng


def test_fast_path_two_syncs_per_block_and_lossless():
    """With an ample cache the verify fast path arms; each fast verify block
    performs exactly ONE host sync inside _verify_block (the all_hit scalar)
    — with the accept/reject readback in the decode loop that is the ≤2
    contract — and the output still exactly matches plain greedy decoding."""
    cfg, target, tparams, eng = _toy_engine(
        slots=eng_slots_all(), draft_len=3)
    rt = eng.runtime
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                                cfg.vocab_size)
    per_block = []
    orig_vb = rt._verify_block

    def spy_vb(tokens, pos, tcache):
        before_sync, before_fast = rt.host_syncs, rt.fast_blocks
        result = orig_vb(tokens, pos, tcache)
        per_block.append((rt.host_syncs - before_sync,
                          rt.fast_blocks > before_fast))
        return result

    rt._verify_block = spy_vb
    ref = greedy_generate(target, tparams, prompt, 16, 64)
    res = eng.submit(Request(prompt=prompt, max_new_tokens=16))
    out, stats = res.token_array(), res.metrics
    eng.close()
    assert out.tolist() == ref.tolist()
    fast = [s for s, is_fast in per_block if is_fast]
    assert fast, "fast path never engaged — check adaptive arming"
    assert max(fast) == 1, f"fast verify block synced more than once: {per_block}"
    # the only other per-iteration readback is the accept/reject argmax
    assert stats["fast_blocks"] == len(fast)
    assert stats["fast_fallbacks"] == 0


def eng_slots_all():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    return cfg.num_moe_layers * cfg.num_experts


def test_fast_path_fallback_is_lossless_when_cache_too_small():
    """Tight cache: fast path may mispredict availability; fallback must
    keep exact losslessness."""
    cfg, target, tparams, eng = _toy_engine(slots=6, draft_len=3)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                                cfg.vocab_size)
    ref = greedy_generate(target, tparams, prompt, 12, 64)
    res = eng.submit(Request(prompt=prompt, max_new_tokens=12))
    out, stats = res.token_array(), res.metrics
    eng.close()
    assert out.tolist() == ref.tolist()
    assert stats["on_demand_loads"] > 0      # the tight cache did miss


def test_hot_path_never_reads_resident_expert_weights():
    """The verify paths must read expert weights only from the cache slot
    buffers: zeroing the resident copies after engine construction must not
    change the output.  precompile=False so the fast path is traced AFTER
    the zeroing — an init-time trace would bake the real weights in as
    constants and mask exactly the regression this test exists to catch."""
    cfg, target, tparams, eng = _toy_engine(slots=eng_slots_all(),
                                            precompile=False)
    rt = eng.runtime
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                                cfg.vocab_size)
    ref = greedy_generate(target, tparams, prompt, 10, 64)
    # wipe the device-resident expert tensors (store already copied them)
    for n in rt.store.names:
        rt.tparams["layers"]["moe"][n] = \
            jnp.zeros_like(rt.tparams["layers"]["moe"][n])
    res = eng.submit(Request(prompt=prompt, max_new_tokens=10))
    eng.close()
    assert res.tokens == ref.tolist()


# ---------------------------------------------------------------------------
# prefetcher drain
# ---------------------------------------------------------------------------

class _SlowStore(HostExpertStore):
    def fetch(self, keys):
        time.sleep(0.05)                  # expose the popped-mid-execute race
        return super().fetch(keys)


def test_drain_waits_for_inflight_tasks():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = _SlowStore(cfg, tparams)
    cache = ExpertCache(16, store.buffer_shapes(), jnp.float32,
                        table_shape=(store.num_layers, store.num_experts))
    pf = Prefetcher(store, cache, mode="worker", batched=True)
    keys = [(0, 0), (0, 1), (1, 2), (2, 3), (3, 4)]
    for k in keys:
        pf.submit([k])
    pf.drain()                            # must cover mid-_execute tasks
    assert all(cache.contains(k) for k in keys)
    assert pf.loaded_count == len(keys)
    pf.stop()


def test_drain_no_busy_wait_completes_quickly_when_idle():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    cache = ExpertCache(4, store.buffer_shapes(), jnp.float32)
    pf = Prefetcher(store, cache, mode="worker")
    t0 = time.perf_counter()
    pf.drain()
    assert time.perf_counter() - t0 < 1.0
    pf.stop()


# ---------------------------------------------------------------------------
# HostExpertStore: staging fetch + strip_experts isolation
# ---------------------------------------------------------------------------

def test_fetch_staging_survives_double_buffering():
    """A fetched batch stays valid while the NEXT fetch writes the other
    staging buffer (the overlap contract insert relies on)."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    a = store.fetch([(0, 0), (1, 1)])
    snap = {n: arr.copy() for n, arr in a.items()}
    b = store.fetch([(2, 2), (3, 3), (0, 5)])   # other buffer
    for n in store.names:
        np.testing.assert_array_equal(a[n], snap[n])
        np.testing.assert_array_equal(b[n][0], store._store[n][2, 2])
    # contents correct against the raw store
    np.testing.assert_array_equal(a[store.names[0]][1],
                                  store._store[store.names[0]][1, 1])


def test_fetch_staging_is_thread_local():
    """Concurrent fetch from the prefetch worker and the compute loop must
    not overwrite each other's staged batches (regression: a shared staging
    ring let one thread's gather corrupt the other's in-flight batch)."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    stop = threading.Event()
    bad = []

    def hammer():
        while not stop.is_set():
            store.fetch([(0, 0), (1, 1), (2, 2)])

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(200):
            got = store.fetch([(3, 3), (0, 5)])
            time.sleep(0.0005)            # hold the view across other-thread fetches
            for n in store.names:
                if not np.array_equal(got[n][0], store._store[n][3, 3]):
                    bad.append(n)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not bad, f"staged batch corrupted by concurrent fetch: {bad[:3]}"


def test_fetch_grows_staging_for_large_batches():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams, staging_batch=2)
    keys = [(l, e) for l in range(store.num_layers)
            for e in range(store.num_experts)][:20]
    got = store.fetch(keys)
    for i, (l, e) in enumerate(keys):
        np.testing.assert_array_equal(got[store.names[0]][i],
                                      store._store[store.names[0]][l, e])


@pytest.fixture(scope="module")
def mixtral_moe_params():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    tparams = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, tparams


def _store_as(cfg, tparams, dtype):
    """A store over the reduced mixtral's experts cast to ``dtype``."""
    moe = {n: np.asarray(a).astype(dtype)
           for n, a in tparams["layers"]["moe"].items()
           if n in ("wg", "wu", "wd")}
    return HostExpertStore(cfg, {"layers": {"moe": moe}})


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16],
                         ids=["float32", "bfloat16"])
def test_fetch_allocates_no_batch_sized_temporary(mixtral_moe_params, dtype):
    """Once the thread's ring exists, a fetch copies into it and allocates
    less than one staged row of one tensor (``np.take(out=...)`` in its
    default mode allocates the whole batch of each tensor, then copies)."""
    store = _store_as(*mixtral_moe_params, dtype)
    keys = [(0, 1), (1, 2), (2, 3)]
    store.fetch(keys)                      # warm-up: allocates the ring
    row_bytes = min(store._store[n][0, 0].nbytes for n in store.names)
    tracemalloc.start()
    try:
        got = store.fetch(keys)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < row_bytes, (peak, row_bytes)
    for n in store.names:
        np.testing.assert_array_equal(got[n][2], store._store[n][2, 3])


_FETCH_CASES = {
    "across-layers": lambda L, E: [(0, 0), (1, E - 1), (L - 1, 2)],
    "out-of-order": lambda L, E: [(L - 1, E - 1), (0, 3), (1, 0), (0, 1)],
    "repeated": lambda L, E: [(1, 2), (0, 5), (1, 2), (1, 2)],
    "expert-E": lambda L, E: [(0, E)],
    "layer-L": lambda L, E: [(L, 0)],
    "expert-negative": lambda L, E: [(0, -1)],
    "layer-negative": lambda L, E: [(-1, 0)],
    "one-bad-among-good": lambda L, E: [(0, 0), (1, E), (1, 1)],
}
_FETCH_BAD = {"expert-E", "layer-L", "expert-negative", "layer-negative",
              "one-bad-among-good"}


@pytest.mark.parametrize("case", list(_FETCH_CASES))
@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16,
                                   ml_dtypes.float8_e4m3fn],
                         ids=["bfloat16", "float8"])
def test_fetch_stages_exact_rows_or_rejects_the_key(mixtral_moe_params,
                                                   dtype, case):
    """Staged rows are the store's bytes for every key, in the keys'
    order, repeats included; a key whose layer or expert is out of range
    raises before anything is staged, instead of reading another expert."""
    store = _store_as(*mixtral_moe_params, dtype)
    L, E = store.num_layers, store.num_experts
    keys = _FETCH_CASES[case](L, E)
    before = store.fetch([(0, 0)])         # the ring's other buffer
    snap = {n: a.copy() for n, a in before.items()}
    if case in _FETCH_BAD:
        with pytest.raises(IndexError):
            store.fetch(keys)
        store.fetch([(1, 1)])              # lands in the other buffer only
        for n in store.names:              # if the failed fetch took none
            np.testing.assert_array_equal(before[n].view(np.uint8),
                                          snap[n].view(np.uint8))
        return
    got = store.fetch(keys)
    for n in store.names:
        assert got[n].dtype == dtype and got[n].shape[0] == len(keys)
        for j, (l, e) in enumerate(keys):
            np.testing.assert_array_equal(got[n][j].view(np.uint8),
                                          store._store[n][l, e].view(np.uint8))


def test_strip_experts_does_not_mutate_original():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    store = HostExpertStore(cfg, tparams)
    shapes_before = {n: tparams["layers"]["moe"][n].shape
                     for n in store.names}
    out = store.strip_experts(tparams)
    for n in store.names:
        assert tparams["layers"]["moe"][n].shape == shapes_before[n]
        assert out["layers"]["moe"][n].shape == (0,)
    # isolation in the other direction too: mutating the copy's dicts must
    # not leak into the original
    out["layers"]["moe"]["gate"] = None
    assert tparams["layers"]["moe"]["gate"] is not None
