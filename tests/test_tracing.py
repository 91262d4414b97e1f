"""The serving loop's profiler spans and its sync and transfer counters.

Spans: serving under ``jax.profiler`` names every stage (prefill, draft,
prediction, verify, fused round, load wave), all on the serving thread;
load waves nest in a verify or a prefill, predictions in a draft.  Syncs: every device->host conversion
of a jax array the serving thread makes during a turn is counted in
``host_syncs``.  Transfers: ``h2d_bytes`` per request tiles the engine's
cumulative counter, and a resident key transfers nothing."""
import contextlib
import threading

import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from conftest import make_draft_for
from repro.configs.registry import get_config
from repro.core.engine import Engine, EngineConfig, Request
from repro.models.registry import build_model

TOK = 10
STAGES = ("spmoe.prefill", "spmoe.draft", "spmoe.predict", "spmoe.verify",
          "spmoe.verify_round", "spmoe.load_wave")


@pytest.fixture(scope="module")
def ms():
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    dcfg = make_draft_for(cfg)
    tparams = build_model(cfg).init(jax.random.PRNGKey(0))
    dparams = build_model(dcfg).init(jax.random.PRNGKey(1))
    prompts = [jax.random.randint(jax.random.PRNGKey(2 + i), (1, 6), 0,
                                  cfg.vocab_size) for i in range(2)]
    return cfg, dcfg, tparams, dparams, prompts


def _engine(ms, slots):
    cfg, dcfg, tparams, dparams, _ = ms
    return Engine(EngineConfig(model=cfg, draft=dcfg, decode="sd",
                               offload="spmoe", cache_slots=slots,
                               draft_len=3, max_seq=64), tparams, dparams)


def _reqs(prompts):
    return [Request(prompt=p, max_new_tokens=TOK, request_id=f"r{i}")
            for i, p in enumerate(prompts)]


def _ample(ms):
    cfg = ms[0]
    return cfg.num_moe_layers * cfg.num_experts


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _host_lines(log_dir):
    """[(name, start_ns, end_ns)] per host thread of the recorded trace."""
    from perfbench import trace
    pd = jax.profiler.ProfileData.from_file(trace.find_xplane(str(log_dir)))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events if e.name.startswith("spmoe.")]
            if evs:
                out.append(evs)
    return out


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_serving_spans_name_every_stage_and_nest(ms, tmp_path):
    """A fresh engine with every expert fitting: both prefills miss (load
    waves), drafting prefetches through the worker, and once both sessions
    are armed their blocks verify in fused rounds."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with _engine(ms, _ample(ms)) as eng:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            res = eng.serve_all(_reqs(ms[4]), concurrency=2)
        finally:
            jax.profiler.stop_trace()
        prefetched = eng.runtime.prefetcher.loaded_count
    assert all(r.finish_reason == "length" for r in res)
    assert prefetched, "the worker loaded nothing while traced"
    lines = _host_lines(tmp_path)
    names = {ev[0] for evs in lines for ev in evs}
    assert names == set(STAGES)
    # the prefetch worker opens none: every span is the serving thread's
    assert len(lines) == 1, "stage spans opened on more than one thread"
    evs = lines[0]
    outer = [e for e in evs if e[0] in ("spmoe.verify", "spmoe.prefill",
                                        "spmoe.verify_round")]
    waves = [e for e in evs if e[0] == "spmoe.load_wave"]
    assert all(any(_inside(w, o) for o in outer) for w in waves)
    assert any(_inside(w, o) for w in waves for o in outer
               if o[0] == "spmoe.prefill")
    # drafting and verifying never overlap: one serving thread, in turn
    drafts = [e for e in evs if e[0] == "spmoe.draft"]
    predicts = [e for e in evs if e[0] == "spmoe.predict"]
    assert all(any(_inside(p, d) for d in drafts) for p in predicts)
    assert not any(_inside(d, o) or _inside(o, d)
                   for d in drafts for o in outer)


# ---------------------------------------------------------------------------
# sync honesty
# ---------------------------------------------------------------------------

class ConversionSpy:
    """Counts device->host conversions of jax arrays made on the thread
    that built it: the ``_value`` property (``int()``, ``bool()``,
    ``.tolist()``, ``__array__``) and numpy's ``asarray`` / ``array``, which
    on the CPU read a jax array through the buffer protocol without
    ``_value``.  A conversion nested in another counts once."""

    def __init__(self, monkeypatch):
        self.count = 0
        self._depth = 0
        self._tid = threading.get_ident()
        value = ArrayImpl._value

        def spied_value(arr):
            with self._converting():
                return value.fget(arr)

        monkeypatch.setattr(ArrayImpl, "_value", property(spied_value))
        for name in ("asarray", "array"):
            monkeypatch.setattr(np, name, self._wrap(getattr(np, name)))

    @contextlib.contextmanager
    def _converting(self):
        mine = threading.get_ident() == self._tid
        if mine:
            self._depth += 1
            if self._depth == 1:
                self.count += 1
        try:
            yield
        finally:
            if mine:
                self._depth -= 1

    def _wrap(self, fn):
        def spied(a, *args, **kw):
            if isinstance(a, jax.Array):
                with self._converting():
                    return fn(a, *args, **kw)
            return fn(a, *args, **kw)
        return spied


@pytest.mark.parametrize("slots", ["tight", "ample"])
def test_every_conversion_of_a_turn_is_a_counted_sync(ms, monkeypatch,
                                                      slots):
    """Prefills, solo turns (tight: slow path, load waves, predictor
    readbacks) and fused rounds (ample, once armed): per call, the spy's
    conversion count equals the ``host_syncs`` delta."""
    n_slots = 6 if slots == "tight" else _ample(ms)
    prompts = ms[4]
    with _engine(ms, n_slots) as eng:
        rt = eng.runtime
        eng.serve_all(_reqs(prompts), concurrency=2)     # compile, warm
        spy = ConversionSpy(monkeypatch)
        calls = []

        def counted(fn, *args):
            c0, s0 = spy.count, rt.host_syncs
            out = fn(*args)
            calls.append((fn.__name__, spy.count - c0, rt.host_syncs - s0))
            return out

        sts = [counted(rt.start_session, p, TOK) for p in prompts]
        while not all(st.finished for st in sts):
            if slots == "tight":
                for st in sts:
                    counted(rt.session_turn, st)
            else:
                counted(rt.session_turns, sts)
        for st in sts:
            rt.finish_session(st)
        monkeypatch.undo()
    assert any(n for _, n, _ in calls)
    assert all(n == s for _, n, s in calls), calls
    if slots == "ample":
        assert rt.verify_rounds and rt.fast_blocks


# ---------------------------------------------------------------------------
# h2d_bytes
# ---------------------------------------------------------------------------

def test_h2d_bytes_tile_and_a_resident_key_transfers_nothing(ms):
    with _engine(ms, 6) as eng:
        rt = eng.runtime
        cache = rt.cache
        before = rt.counters()["h2d_bytes"]
        res = eng.serve_all(_reqs(ms[4]), concurrency=2)
        moved = rt.counters()["h2d_bytes"] - before
        assert moved > 0
        assert sum(r.metrics.h2d_bytes for r in res) == moved
        assert eng.metrics().h2d_bytes == moved
        # a prefetch re-submitting a resident key, and a direct insert of
        # it, transfer nothing
        key = next(iter(cache.table))
        h0 = cache.h2d_bytes
        task = rt.prefetcher.submit([key])
        assert rt.prefetcher.wait_task(task, timeout=30)
        stats = {}
        cache.insert([key], rt.store.fetch([key]), stats=stats)
        assert cache.h2d_bytes == h0
        assert task.stats.get("h2d_bytes", 0) == 0
        assert stats.get("h2d_bytes", 0) == 0
        # a fresh key transfers one expert's bytes, credited to the caller
        cfg = ms[0]
        fresh = next((l, e) for l in range(cfg.num_moe_layers)
                     for e in range(cfg.num_experts)
                     if (l, e) not in cache.table)
        cache.insert([fresh], rt.store.fetch([fresh]), stats=stats)
        assert cache.h2d_bytes - h0 == stats["h2d_bytes"] == \
            rt.store.expert_bytes()


# ---------------------------------------------------------------------------
# compact_bytes
# ---------------------------------------------------------------------------

def test_compact_bytes_count_the_kernels_gather_per_request(ms):
    """A pool no larger than a block's T*k choices gathers nothing; a larger
    one gathers M = T*k experts' weights in every cache_moe call, which the
    requests' counters tile, and one fast decode block is one call per MoE
    layer."""
    cfg = ms[0]
    with _engine(ms, 6) as eng:                 # 6 <= T*k = 4 * 2
        res = eng.serve_all(_reqs(ms[4]), concurrency=1)
        assert eng.runtime.counters()["compact_bytes"] == 0
        assert all(r.metrics.compact_bytes == 0 for r in res)
    with _engine(ms, _ample(ms)) as eng:        # 16 slots > 8
        rt = eng.runtime
        res = eng.serve_all(_reqs(ms[4]), concurrency=1)
        total = rt.counters()["compact_bytes"]
        per_call = 4 * cfg.num_experts_per_tok * rt.store.expert_bytes()
        assert total > 0 and total % per_call == 0
        assert sum(r.metrics.compact_bytes for r in res) == total
        assert eng.metrics().compact_bytes == total
        # one armed block on the warm cache, every expert resident
        st = rt.start_session(ms[4][0], TOK)
        rt.session_turn(st)                     # delivers the prefill token
        st.fast_ok = True
        c0, i0, f0 = rt.compact_bytes, st.io["compact_bytes"], rt.fast_blocks
        rt.session_turn(st)
        rt.finish_session(st)
        assert rt.fast_blocks == f0 + 1
        assert rt.compact_bytes - c0 == st.io["compact_bytes"] - i0 == \
            cfg.num_moe_layers * per_call
