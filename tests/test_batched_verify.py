"""Batched cross-session verification (the fused Engine.serve rounds):
every scheduling round gathers the ready sessions' draft blocks into ONE
``_verify_fast_batched`` dispatch — one routing pass, one page-table gather,
one cache_moe launch, ≤2 host syncs per ROUND (not per session).  Asserted
here: bit-identical losslessness vs solo serving across all 15 decode x
offload combinations under ragged draft lengths, per-session miss fallback
that leaves batchmates on the fast path, the ≤2-syncs-per-round contract,
one fused launch per all-hit round, and a hypothesis property sweep over
schedules."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hs

from conftest import make_draft_for
from repro.configs.registry import get_config
from repro.core.engine import (DECODE_POLICIES, OFFLOAD_POLICIES, Engine,
                               EngineConfig, Request)
from repro.core.sd import greedy_generate
from repro.models.registry import build_model

TOK = 10
PLENS = (4, 6, 9)        # ragged prompts: ragged prefills AND, with
                         # sd-adaptive, ragged per-session draft lengths

_MS = None


def _ms():
    """Module-memoized target/draft params, three ragged prompts, greedy
    refs.  A plain function (not a fixture) so the hypothesis property test
    can use it too — the stub's @given hides the signature from pytest's
    fixture resolution."""
    global _MS
    if _MS is None:
        cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
        dcfg = make_draft_for(cfg)
        target = build_model(cfg)
        tparams = target.init(jax.random.PRNGKey(0))
        dparams = build_model(dcfg).init(jax.random.PRNGKey(1))
        prompts = [jax.random.randint(jax.random.PRNGKey(2 + i), (1, n), 0,
                                      cfg.vocab_size)
                   for i, n in enumerate(PLENS)]
        refs = [greedy_generate(target, tparams, p, TOK, 64).tolist()
                for p in prompts]
        _MS = (cfg, dcfg, tparams, dparams, prompts, refs)
    return _MS


@pytest.fixture(scope="module")
def ms():
    return _ms()


def _engine(ms, decode="sd", offload="spmoe", slots=None, **over):
    cfg, dcfg, tparams, dparams, _, _ = ms
    if slots is None:
        slots = cfg.num_moe_layers * cfg.num_experts    # ample
    over.setdefault("draft_len", 3)
    over.setdefault("max_seq", 64)
    return Engine(EngineConfig(model=cfg, draft=dcfg, decode=decode,
                               offload=offload, cache_slots=slots, **over),
                  tparams, dparams)


def _reqs(prompts, n=TOK):
    return [Request(prompt=p, max_new_tokens=n) for p in prompts]


# ---------------------------------------------------------------------------
# batched rounds are lossless — all 15 decode x offload combinations,
# ragged prompts, tight cache (mixed hit/miss + per-session fallbacks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("offload", OFFLOAD_POLICIES)
@pytest.mark.parametrize("decode", DECODE_POLICIES)
def test_batched_rounds_lossless_all_combinations(ms, decode, offload):
    """The acceptance contract: two ragged sessions fused per round emit the
    token stream of serving each alone (the solo greedy reference) on all 15
    combinations.  A tight cache keeps the offload combos under miss and
    eviction pressure, so rounds mix fast commits with solo fallbacks."""
    _, _, _, _, prompts, refs = ms
    picks = [0, 2]                         # prompt lengths 4 and 9
    with _engine(ms, decode=decode, offload=offload, slots=8,
                 max_draft_len=5) as eng:
        res = eng.serve_all(_reqs([prompts[i] for i in picks]),
                            concurrency=2)
    for r, i in zip(res, picks):
        assert r.tokens == refs[i], (decode, offload)
        assert r.finish_reason == "length"
        assert r.metrics.tokens == TOK


def test_batched_rounds_ragged_adaptive_lengths(ms):
    """sd-adaptive diverges the sessions' draft lengths, so fused rounds see
    ragged [1, T_i] blocks; three sessions stay bit-identical to solo and
    the fused path really engaged (it traced)."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms, decode="sd-adaptive", offload="spmoe",
                 min_draft_len=1, max_draft_len=5) as eng:
        rt = eng.runtime
        res = eng.serve_all(_reqs(prompts), concurrency=3)
        assert rt._batched_traces > 0, "fused cross-session path never ran"
    for r, ref in zip(res, refs):
        assert r.tokens == ref
        assert r.finish_reason == "length"


# ---------------------------------------------------------------------------
# per-session miss fallback: one session falls back alone, batchmates commit
# ---------------------------------------------------------------------------

def test_missing_session_falls_back_alone(ms):
    """Force the fused all-hit flag False for session 1 on every round: that
    session must re-verify on the slow path (still lossless) while session 0
    keeps committing fused fast blocks with zero fallbacks."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts[:2]), concurrency=2)   # warm + arm
        forced = []
        orig = rt._verify_fast_batched

        def force_miss(*args):
            logits, ok, tcs, hists, nact = orig(*args)
            if ok.shape[0] >= 2:
                ok = ok.at[1].set(False)
                forced.append(1)
            return logits, ok, tcs, hists, nact

        rt._verify_fast_batched = force_miss
        res = eng.serve_all(_reqs(prompts[:2]), concurrency=2)
        rt._verify_fast_batched = orig
    assert forced, "no fused round ran on the warm engine"
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    assert res[1].metrics.fast_fallbacks >= 1
    assert res[0].metrics.fast_fallbacks == 0
    assert res[0].metrics.fast_blocks >= 1


# ---------------------------------------------------------------------------
# sync contract: ≤2 host syncs per ROUND (not per session)
# ---------------------------------------------------------------------------

def test_round_sync_contract_two_syncs_per_round(ms):
    """On the warm all-hit path a fused round serving two sessions performs
    at most 2 verify syncs TOTAL (the per-session all-hit vector and the
    accept argmax, one readback each) — the solo contract was 2 per block,
    i.e. 2·N per round.  At least one round must commit both sessions'
    blocks inside that budget.  Drafting's syncs (host_syncs only) are
    outside this contract."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts[:2]), concurrency=2)   # warm + arm
        per_round = []
        orig = rt.session_turns

        def spy(sts):
            s0, b0 = rt.verify_syncs, rt.fast_blocks
            out = orig(sts)
            per_round.append((rt.verify_syncs - s0, rt.fast_blocks - b0))
            return out

        rt.session_turns = spy
        res = eng.serve_all(_reqs(prompts[:2]), concurrency=2)
        rt.session_turns = orig
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    verifying = [(s, b) for s, b in per_round if b > 0]
    assert verifying, "no round committed a fast block"
    assert max(s for s, _ in verifying) <= 2, \
        f"a round exceeded 2 verify syncs: {per_round}"
    assert any(b == 2 for s, b in verifying if s <= 2), \
        f"no round committed both sessions within 2 syncs: {per_round}"


def test_fused_trace_shared_across_length_permutations(ms):
    """Ragged rounds are canonicalized by block length before the fused
    dispatch, so a (2,4) round and its (4,2) permutation reuse ONE compiled
    executable — sd-adaptive's drifting per-session lengths must not
    retrace per ordering (the analogue of the table-scatter bucket fix)."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts[:2]), concurrency=2)   # warm + arm
        st1 = rt.start_session(prompts[0], 8)
        st2 = rt.start_session(prompts[1], 8)
        rt.session_turns([st1, st2])       # deliver the prefill chunks
        t0 = rt._batched_traces
        st1.n, st2.n = 2, 4                # ragged round ...
        rt.session_turns([st1, st2])
        st1.n, st2.n = 4, 2                # ... and its permutation
        rt.session_turns([st1, st2])
        assert rt._batched_traces - t0 == 1, \
            "permuted block lengths recompiled the fused round"
        rt.finish_session(st1)
        rt.finish_session(st2)


def test_one_fused_launch_per_round_on_all_hit_path(ms):
    """Warm, ample cache: every verifying round dispatches exactly one
    fused verify launch (was one per session) and falls back never."""
    _, _, _, _, prompts, refs = ms
    with _engine(ms) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts[:2]), concurrency=2)   # warm + arm
        r0, l0, f0 = rt.verify_rounds, rt.round_launches, rt.fast_fallbacks
        res = eng.serve_all(_reqs(prompts[:2]), concurrency=2)
        rounds = rt.verify_rounds - r0
        launches = rt.round_launches - l0
        assert rt.fast_fallbacks == f0, "warm all-hit serve fell back"
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    assert rounds > 0
    assert launches == rounds, \
        f"{launches} verify launches over {rounds} rounds (want 1/round)"


# ---------------------------------------------------------------------------
# property sweep: random decode x offload x schedule stays bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(decode=hs.sampled_from(DECODE_POLICIES),
       offload=hs.sampled_from([o for o in OFFLOAD_POLICIES if o != "none"]),
       tight=hs.booleans(),
       nreq=hs.integers(2, 3),
       tok=hs.integers(4, TOK))
def test_property_batched_rounds_bit_identical(decode, offload, tight, nreq,
                                               tok):
    """Randomly drawn decode x offload x cache-pressure x round-size x
    budget: every session's stream is bit-identical to its solo greedy
    reference, and per-request token budgets are honoured exactly."""
    ms = _ms()
    cfg, _, _, _, prompts, refs = ms
    slots = 8 if tight else cfg.num_moe_layers * cfg.num_experts
    with _engine(ms, decode=decode, offload=offload, slots=slots,
                 max_draft_len=5) as eng:
        res = eng.serve_all(_reqs(prompts[:nreq], n=tok), concurrency=nreq)
    for r, ref in zip(res, refs):
        assert r.tokens == ref[:tok], (decode, offload, tight, nreq, tok)
        assert r.metrics.tokens == tok
