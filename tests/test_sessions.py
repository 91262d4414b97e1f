"""Concurrent sessions on one warm cache (Engine.serve round-robin
scheduler) plus the serving-loop correctness fixes that rode along:
interleaved sessions bit-identical to their solo greedy references on every
decode x offload combination, per-request Metrics isolation under
interleaving, the ≤2-syncs-per-block contract with concurrency on,
abandoned streams reporting finish_reason="aborted" (engine stays
reusable), Prefetcher.submit after stop() no longer hanging drain(),
Metrics.add preserving the cutoff_layer echo, and the sd-adaptive
draft-length ladder pre-traced at engine init."""
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import make_draft_for
from repro.configs.registry import get_config
from repro.core.cache import ExpertCache
from repro.core.engine import (DECODE_POLICIES, OFFLOAD_POLICIES, Engine,
                               EngineConfig, Metrics, Request)
from repro.core.offload import HostExpertStore
from repro.core.prefetcher import Prefetcher
from repro.core.sd import greedy_generate
from repro.models.registry import build_model

TOK = 10


@pytest.fixture(scope="module")
def ms():
    """Reduced-mixtral target/draft params, two prompts, their greedy refs."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    dcfg = make_draft_for(cfg)
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    dparams = build_model(dcfg).init(jax.random.PRNGKey(1))
    prompts = [jax.random.randint(jax.random.PRNGKey(2 + i), (1, 6), 0,
                                  cfg.vocab_size) for i in range(2)]
    refs = [greedy_generate(target, tparams, p, TOK, 64).tolist()
            for p in prompts]
    return cfg, dcfg, tparams, dparams, prompts, refs


def _engine(ms, decode="sd", offload="spmoe", slots=None, **over):
    cfg, dcfg, tparams, dparams, _, _ = ms
    if slots is None:
        slots = cfg.num_moe_layers * cfg.num_experts    # ample
    over.setdefault("draft_len", 3)
    over.setdefault("max_seq", 64)
    return Engine(EngineConfig(model=cfg, draft=dcfg, decode=decode,
                               offload=offload, cache_slots=slots, **over),
                  tparams, dparams)


def _reqs(prompts, **kw):
    return [Request(prompt=p, max_new_tokens=TOK, **kw) for p in prompts]


# ---------------------------------------------------------------------------
# interleaving is lossless — every decode x offload combination
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offload", OFFLOAD_POLICIES)
@pytest.mark.parametrize("decode", DECODE_POLICIES)
def test_interleaved_sessions_lossless_all_combinations(ms, decode, offload):
    """The acceptance contract of the scheduler: two sessions round-robined
    on one warm cache each emit the token stream of serving them alone —
    which is the solo greedy reference — on all 15 combinations.  A tight
    cache keeps the offload combos under real miss/eviction pressure while
    the sessions compete for slots."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms, decode=decode, offload=offload, slots=8,
                 max_draft_len=5) as eng:
        res = eng.serve_all(_reqs(prompts), concurrency=2)
    for r, ref in zip(res, refs):
        assert r.tokens == ref, (decode, offload)
        assert r.finish_reason == "length"
        assert r.metrics.tokens == TOK


def test_serve_yields_interleaved_commit_order(ms):
    """serve() is a real round-robin: session 1 commits tokens before
    session 0 finishes, streams reassemble losslessly from the event
    stream, and last_batch lands in submission order."""
    _, _, _, _, prompts, refs = ms
    reqs = [Request(prompt=p, max_new_tokens=TOK, request_id=f"s{i}")
            for i, p in enumerate(prompts)]
    with _engine(ms) as eng:
        events = list(eng.serve(reqs, concurrency=2))
        res = eng.last_batch
    streams = {"s0": [], "s1": []}
    for rid, tok in events:
        streams[rid].append(tok)
    assert streams["s0"] == refs[0] and streams["s1"] == refs[1]
    first_s1 = next(i for i, (rid, _) in enumerate(events) if rid == "s1")
    last_s0 = max(i for i, (rid, _) in enumerate(events) if rid == "s0")
    assert first_s1 < last_s0, "sessions were served serially, not interleaved"
    assert [r.request_id for r in res] == ["s0", "s1"]
    assert all(r.finish_reason == "length" for r in res)


def test_stop_token_and_admission_beyond_concurrency(ms):
    """A stop token retires one session mid-flight without disturbing its
    neighbours, and a third request is admitted once a slot frees up."""
    _, _, _, _, prompts, refs = ms
    stop = refs[0][4]
    reqs = [Request(prompt=prompts[0], max_new_tokens=TOK,
                    stop_tokens=(stop,)),
            Request(prompt=prompts[1], max_new_tokens=TOK),
            Request(prompt=prompts[0], max_new_tokens=TOK)]
    with _engine(ms) as eng:
        res = eng.serve_all(reqs, concurrency=2)
    assert res[0].tokens == refs[0][:5] and res[0].finish_reason == "stop"
    assert res[1].tokens == refs[1] and res[1].finish_reason == "length"
    assert res[2].tokens == refs[0] and res[2].finish_reason == "length"


# ---------------------------------------------------------------------------
# per-request metrics stay isolated when sessions interleave
# ---------------------------------------------------------------------------

def test_metrics_isolated_under_interleaving(ms):
    """Each interleaved session's Metrics delta equals its solo run on the
    deterministic (schedule-independent) counters, and the per-session
    ledgers tile the engine-cumulative delta exactly — nothing double-
    counted across sessions, nothing dropped."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as solo_eng:
        solo = [solo_eng.submit(r) for r in _reqs(prompts)]
    with _engine(ms) as eng:
        before = eng.metrics()
        res = eng.serve_all(_reqs(prompts), concurrency=2)
        after = eng.metrics()
    for r, s in zip(res, solo):
        assert r.tokens == s.tokens
        for k in ("tokens", "iterations", "drafted", "accepted",
                  "verify_blocks"):
            assert r.metrics[k] == s.metrics[k], k
    # ledger completeness over the synchronously-updated counters (the
    # async I/O counters — prefetched/evictions — can land between turns)
    for k in ("iterations", "drafted", "accepted", "verify_blocks",
              "fast_blocks", "fast_fallbacks", "host_syncs", "verify_syncs",
              "on_demand_loads", "lookups", "hits", "tokens", "requests"):
        assert sum(r.metrics[k] for r in res) == after[k] - before[k], k


# ---------------------------------------------------------------------------
# sync contract survives concurrency
# ---------------------------------------------------------------------------

def test_sync_contract_two_syncs_per_block_with_concurrency(ms):
    """With an ample cache and two interleaved sessions: solo fast blocks
    (the prefills) still perform exactly ONE verify sync inside
    _verify_block, and every batched decode ROUND — which commits BOTH
    sessions' verify blocks in one fused dispatch — performs ≤2 verify
    syncs total, i.e. the old 2-per-block contract became 2-per-round.
    Drafting's syncs count in host_syncs only, outside this contract.  Both
    streams stay lossless."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts), concurrency=2)    # warm cache + arming
        per_block, per_round = [], []
        orig_vb = rt._verify_block
        orig_turns = rt.session_turns

        def spy_vb(tokens, pos, tcache):
            before_sync, before_fast = rt.verify_syncs, rt.fast_blocks
            out = orig_vb(tokens, pos, tcache)
            per_block.append((rt.verify_syncs - before_sync,
                              rt.fast_blocks > before_fast))
            return out

        def spy_turns(sts):
            before_sync, before_fast = rt.verify_syncs, rt.fast_blocks
            out = orig_turns(sts)
            per_round.append((rt.verify_syncs - before_sync,
                              rt.fast_blocks - before_fast))
            return out

        rt._verify_block = spy_vb
        rt.session_turns = spy_turns
        res = eng.serve_all(_reqs(prompts), concurrency=2)
        rt._verify_block = orig_vb
        rt.session_turns = orig_turns
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    fast = [s for s, is_fast in per_block if is_fast]
    assert fast, "solo fast path never engaged (prefill blocks)"
    assert max(fast) == 1, f"fast block synced more than once: {per_block}"
    fused = [(s, b) for s, b in per_round if b == 2]
    assert fused, "no round committed both sessions' blocks fused"
    assert max(s for s, _ in fused) <= 2, \
        f"a fused round exceeded 2 verify syncs: {per_round}"
    assert all(r.metrics.fast_fallbacks == 0 for r in res)


# ---------------------------------------------------------------------------
# abandoned consumers: finish_reason="aborted", engine stays reusable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("decode,offload", [("sd", "spmoe"),
                                            ("greedy", "none")])
def test_abandoned_stream_reports_aborted_and_engine_reusable(
        ms, decode, offload):
    """Regression: GeneratorExit used to hit stream()'s finally with finish
    still at its "length" default.  An abandoned stream must report
    "aborted" — and the engine must keep serving afterwards."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms, decode=decode, offload=offload) as eng:
        g = eng.stream(Request(prompt=prompts[0], max_new_tokens=TOK))
        first = next(g)
        g.close()                       # consumer walks away mid-stream
        res = eng.last_result
        assert res.finish_reason == "aborted"
        assert res.tokens[0] == first and len(res.tokens) < TOK
        assert res.metrics.requests == 1
        res2 = eng.submit(Request(prompt=prompts[0], max_new_tokens=TOK))
        assert res2.tokens == refs[0]
        assert res2.finish_reason == "length"
        assert eng.metrics().requests == 2


def test_serve_close_aborts_active_sessions(ms):
    """Closing the serve() iterator retires every unfinished session as
    "aborted", publishes last_batch, and leaves the engine reusable; a
    never-started iterator leaves last_batch empty, never stale."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        eng.submit(Request(prompt=prompts[0], max_new_tokens=2))
        never_started = eng.serve(_reqs(prompts), concurrency=2)
        never_started.close()
        assert eng.last_batch == []     # not the previous request's results
        it = eng.serve(_reqs(prompts), concurrency=2)
        next(it)
        next(it)                        # both sessions have committed tokens
        it.close()
        res = eng.last_batch
        assert len(res) == 2
        assert all(r is not None and r.finish_reason == "aborted"
                   for r in res)
        r = eng.submit(Request(prompt=prompts[0], max_new_tokens=TOK))
        assert r.tokens == refs[0]


# ---------------------------------------------------------------------------
# prefetcher: submit after stop() must not hang drain()
# ---------------------------------------------------------------------------

def test_prefetcher_submit_after_stop_executes_inline_and_drains(ms):
    """Regression: a task enqueued with no worker thread incremented
    _inflight with nothing left to decrement it, so the next drain() waited
    forever.  submit-after-stop now degrades to synchronous execution."""
    cfg, _, tparams, _, _, _ = ms
    store = HostExpertStore(cfg, tparams)
    cache = ExpertCache(8, store.buffer_shapes(), jnp.float32,
                        table_shape=(store.num_layers, store.num_experts))
    pf = Prefetcher(store, cache, mode="worker", batched=True)
    pf.stop()
    task = pf.submit([(0, 0), (1, 1)])
    assert task is not None and task.done.is_set()
    assert cache.contains((0, 0)) and cache.contains((1, 1))
    t0 = time.perf_counter()
    pf.drain()                          # used to hang forever
    assert time.perf_counter() - t0 < 2.0
    assert pf.loaded_count == 2


# ---------------------------------------------------------------------------
# per-session I/O is attributed to the task OWNER, not the turn it lands in
# ---------------------------------------------------------------------------

def test_prefetch_io_attributed_to_task_owner(ms):
    """Regression (ROADMAP open item): with an async worker, a prefetch load
    could land between two sessions' turns and be charged — via the
    turn-window counter delta — to the wrong session's ledger.  I/O now
    rides on the task: a slowed store guarantees the two sessions' prefetch
    waves interleave across turn boundaries, and each session's ledger must
    equal exactly the loads of the tasks IT submitted, with every eviction
    owned by exactly one session."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms, slots=24) as eng:       # tight-ish: eviction pressure
        rt = eng.runtime
        orig_fetch = rt.store.fetch

        def slow_fetch(keys):
            time.sleep(0.02)                 # push completion past the turn
            return orig_fetch(keys)

        rt.store.fetch = slow_fetch
        owned = {}
        orig_prefetch = rt._prefetch

        def spy_prefetch(st, keys):
            n0 = len(st.inflight)
            orig_prefetch(st, keys)
            owned.setdefault(id(st), []).extend(st.inflight[n0:])

        rt._prefetch = spy_prefetch
        st1 = rt.start_session(prompts[0], TOK)
        st2 = rt.start_session(prompts[1], TOK)
        while not (st1.finished and st2.finished):   # interleave waves
            if not st1.finished:
                rt.session_turn(st1)
            if not st2.finished:
                rt.session_turn(st2)
        rt.finish_session(st1)
        rt.finish_session(st2)
        rt._prefetch = orig_prefetch
        rt.store.fetch = orig_fetch
        for st in (st1, st2):
            want = sum(t.stats.get("prefetched", 0)
                       for t in owned.get(id(st), []))
            assert st.io["prefetched"] == want
        assert st1.io["prefetched"] > 0 and st2.io["prefetched"] > 0
        # totals tile: every load and every eviction has exactly one owner
        assert st1.io["prefetched"] + st2.io["prefetched"] == \
            rt.prefetcher.loaded_count
        assert st1.io["evictions"] + st2.io["evictions"] == \
            rt.cache.evictions


# ---------------------------------------------------------------------------
# Metrics.add keeps the cutoff_layer configuration echo
# ---------------------------------------------------------------------------

def test_metrics_add_preserves_cutoff_echo():
    """Regression: adding a default-constructed Metrics (cutoff_layer=-1)
    used to wipe the configured echo back to -1."""
    m = Metrics(cutoff_layer=3)
    m.add(Metrics())
    assert m.cutoff_layer == 3
    m.add(Metrics(cutoff_layer=5))
    assert m.cutoff_layer == 5


# ---------------------------------------------------------------------------
# sd-adaptive x offload: the whole draft-length ladder is pre-traced
# ---------------------------------------------------------------------------

def test_adaptive_ladder_precompiled(ms):
    """ROADMAP open item closed: engine init pre-traces _verify_fast for
    every draft length in [min_draft_len, max_draft_len], so no adapted
    length retraces under the cache lock mid-serve."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms, decode="sd-adaptive", min_draft_len=1,
                 max_draft_len=3) as eng:
        rt = eng.runtime
        assert rt._fast_traces == 3, "draft-length ladder not pre-traced"
        res = eng.submit(Request(prompt=prompts[0], max_new_tokens=TOK))
        assert res.metrics.fast_blocks >= 1, "fast path never engaged"
        assert rt._fast_traces == 3, \
            "adapted draft length retraced after engine init"
    assert res.tokens == refs[0]
