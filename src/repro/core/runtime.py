"""SP-MoE offload-mode serving engine (paper-faithful runtime).

Combines every paper component end-to-end, for real, on whatever backend JAX
is running on:

  * speculative decoding (batch=1, greedy accept) — core/sd.py semantics;
  * target expert weights offloaded to a HostExpertStore; a fixed-slot
    ExpertCache with LRU lives on device;
  * drafting-stage cross-model prediction: draft gate-input taps × target
    gating networks -> prefetch tasks for layers 0..cutoff (Algorithm 1);
  * pipelined prefetching: async worker + batched I/O (Algorithm 2);
  * cached-first expert computation ordering (§4.3): the hit-experts' FFN is
    dispatched (asynchronously) while misses stream in, then the miss part is
    computed — compute/IO overlap without waiting on full availability.

Verification hot path (device-resident)
---------------------------------------
Verification latency is bounded by how well expert loading overlaps compute,
so the verify step must not re-enter the host per layer.  Two paths share
the slot-indexed grouped kernel ``kernels/cache_moe.py``:

* **fast path** — a single jitted ``lax.scan`` over all MoE layers.
  Routing (``gate_topk``), slot translation (a gather from the cache's
  device-side page table ``table_dev [L, E] -> slot | -1``), the hit mask,
  the cached-expert FFN, and the per-layer history/hit accounting all stay
  on device.  The block also computes an ``all_hit`` flag; the **only** host
  sync in the block is reading that one scalar.  If every routed expert was
  cache-resident (the common case once prefetching is warm) the block's
  logits and KV-cache update are committed as-is — together with the
  accept/reject readback that is 2 verify syncs per block (the
  ``verify_syncs`` contract below).  If some expert was missing, the
  speculative fast block is discarded (its KV cache is a pure-functional
  copy, so nothing to undo) and the slow path re-runs the block with
  on-demand loading.

* **slow path (miss resolution)** — the layer-by-layer loop: routing ids are
  read back once per layer (the miss-resolution sync), missing experts are
  fetched in cache-capacity-bounded waves while the already-dispatched
  cached-first compute proceeds underneath, and each wave's share of the
  block is added via the same slot-indexed kernel with the wave's slots
  unmasked.  A block that resolves with zero misses re-arms the fast path
  (adaptive: cold caches never pay the speculative double-compute, warm
  caches never pay per-layer syncs).

Expert weights are *never* sliced out of the resident target params on the
hot path — both paths read expert weights exclusively from the ExpertCache
slot buffers, which is what makes the offload story honest.

Baseline policies (for the paper's comparisons) plug into the same loop:
  on-demand (Mixtral-Offloading), moe-infinity (historical top-k,
  request-level, depth-unbounded), adapmoe (same-model next-layer gating,
  synchronous prefetch — always the slow path, per its design).

Host-sync accounting: every blocking device->host readback on the DECODE
path goes through ``_readback`` and is counted in ``host_syncs`` — the
draft loop's argmax, both of the predictor's readbacks per predicted layer,
the prefill's first token, the slow path's routing ids and the verify
readbacks alike (tests/test_tracing.py checks the count against every
conversion of a jax array during a turn).  ``verify_syncs`` counts the
subset made while verifying and accepting a block, and carries the
contract: ≤2 per verify block (the all-hit flag and the accept argmax, 1
inside a fast block) and ≤2 per fused round.  Metrics-plane readbacks
(each session's device-side fast-hit accumulator, committed by
``finish_session`` once at retirement) sit outside the decode loop and are
intentionally not counted.

Stage spans: ``jax.profiler.TraceAnnotation`` at the call sites names each
serving stage on the profiler's host timeline, on the device ops' clock —
``spmoe.prefill`` / ``spmoe.draft`` / ``spmoe.predict`` (inside a draft:
one draft token's expert prediction, lookups and prefetch submissions) /
``spmoe.verify`` / ``spmoe.verify_round`` / ``spmoe.load_wave``, all opened
by the serving thread, so they nest.  With no profiler active a span costs
its context manager only (its arguments are encoded only while tracing).

This engine is the *internal* offload layer: construct it from an
``EngineConfig`` (core/engine.py) — the public request/stream API is
``repro.core.engine.Engine``, which owns one OffloadEngine and serves many
requests against its warm cache.  The decode axis (greedy | sd |
sd-adaptive) is honoured here too: greedy runs 1-token verify blocks with
no drafting stage (note SP-MoE's prefetch signal IS the drafting stage, so
``greedy × spmoe`` degenerates to on-demand loading), sd-adaptive drives
the same EWMA draft-length controller as core/sd.py.

State is split into two planes so sessions can interleave on one warm
cache: everything a single request mutates lives in a :class:`DecodeState`
(KV/draft caches, position, draft-length controller, request-level
MoE-Infinity history, fast-path arming, and the device-side fast-hit
accumulator), while the engine keeps only the shared runtime (cache,
prefetcher, compiled steps) and cumulative counters.  The turn API —
``start_session`` / ``session_turn`` / ``finish_session`` — advances any
session by one committed verify block at a time; ``generate_stream`` is the
single-session wrapper, and ``Engine.serve`` (core/engine.py) is the
round-robin multi-session scheduler on top.

Batched cross-session verification: ``session_turns`` advances a whole
scheduling round at once — each ready session drafts sequentially, then
every armed session's block is verified in ONE fused dispatch
(``_verify_fast_batched``: per-session attention against each session's own
KV cache, but one concatenated [ΣT_i, ·] row batch through routing, the
``table_dev`` gather, the ``cache_moe`` kernel and the head), with ≤2
verify syncs for the whole round instead of 2·N.  Row-wise ops are bit-stable
under concatenation, so batched rounds stay lossless — bit-identical to
serving every session alone; a session that misses falls back alone to the
slow path without dragging its batchmates off the fast path.  Per-session
I/O (prefetched / evictions) is attributed to the session that caused it
via task-owner stats, not to whoever's turn an async load landed in.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import ExpertCache, ExpertKey
from repro.core.chaos import ChaosInjector, ExpertLoadError
from repro.core.cutoff import solve_cutoff
from repro.core.engine import (RUNTIME_COUNTER_KEYS, DecodePolicy,
                               EngineConfig)
from repro.core.offload import HostExpertStore
from repro.core.predictor import ExpertPredictor
from repro.core.prefetcher import Prefetcher
from repro.core import sd as S
from repro.kernels import ops
from repro.kernels.cache_moe import compacted_slots
from repro.models import layers as L
from repro.models.moe import ffn_forward, gate_topk
from repro.models.transformer import DecoderLM

POLICIES = ("spmoe", "adapmoe", "moe-infinity", "on-demand")

# counters() keys — single source of truth in core/engine.py (the Engine's
# per-request delta iterates the same tuple)
_COUNTER_KEYS = RUNTIME_COUNTER_KEYS


@dataclasses.dataclass
class DecodeState:
    """The per-session plane of the offload engine: everything exactly one
    in-flight request mutates while decoding.  The engine-global plane (the
    warm ExpertCache, Prefetcher, compiled step functions, cumulative
    counters) is shared by every session; interleaving sessions block-by-
    block is safe because ``session_turn`` re-binds this state before
    touching any shared helper.

    ``history_dev`` / ``fast_ok`` / ``fast_penalty`` / ``fast_active_dev``
    used to live on the engine itself (PR 2/3) — request-level state that
    silently became engine-global.  They are per-session now: one session's
    fast-path misprediction no longer disarms another mid-block, and the
    MoE-Infinity history really is request-level, as that baseline defines
    it.  What stays global is the *warm hint* (`OffloadEngine._fast_hint`),
    seeding newly admitted sessions' arming from the shared cache's observed
    residency."""
    max_new: int
    tcache: Any
    request_id: Optional[str] = None  # span argument, never read otherwise
    dcache: Any = None
    cur: Optional[jax.Array] = None
    pos: int = 0
    n: int = 0                        # current draft length (0 = greedy)
    acc_ewma: float = 0.5
    emitted_total: int = 0
    pending: Optional[List[int]] = None   # prefill chunk awaiting delivery
    history_dev: Any = None           # MoE-Infinity request-level history
    fast_ok: bool = False
    fast_penalty: int = 0
    fast_active_dev: Any = None       # device-side fast-path hit accumulator
    fast_blocks: int = 0              # session's fast blocks (commit gate)
    inflight: List[Any] = dataclasses.field(default_factory=list)
    finished: bool = False
    committed: bool = False
    # owner-attributed I/O ledger: evictions this session's synchronous
    # (on-demand wave) inserts caused land here directly; its prefetch
    # tasks' stats are folded in by finish_session after done.wait().  This
    # replaces turn-window counter deltas for the per-request
    # prefetched/evictions metrics, which mis-attributed async loads landing
    # between two sessions' turns (ROADMAP open item, closed).
    io: Dict[str, int] = dataclasses.field(default_factory=lambda: {
        "prefetched": 0, "evictions": 0, "prefetch_evicted_unused": 0,
        "h2d_bytes": 0, "compact_bytes": 0})


class OffloadEngine:
    def __init__(self, config: EngineConfig, tparams, dparams, *,
                 target=None, draft=None):
        """``target``/``draft`` accept the caller's already-built models
        (core/engine.py passes its own); built here when omitted.  Greedy
        decode has no drafting stage, so no draft model exists at all."""
        cfg = config.model
        assert config.offload in POLICIES, config.offload
        assert cfg.is_moe, "offload engine targets MoE models"
        self.config = config
        self.cfg = cfg
        self.policy = config.offload
        self.decode = config.decode
        self.draft_len = config.initial_draft_len
        self.max_seq = config.max_seq
        self.target = target if target is not None else DecoderLM(cfg)
        if config.needs_draft:
            self.draft = draft if draft is not None \
                else DecoderLM(config.resolved_draft())
        else:
            self.draft = None
        self.draft_cfg = self.draft.cfg if self.draft is not None else None
        self.tparams, self.dparams = tparams, dparams
        # resilience plane: one seeded fault injector shared by the store,
        # the cache and the prefetcher (None = chaos off, zero overhead)
        self.chaos = ChaosInjector(config.chaos) \
            if config.chaos is not None and config.chaos.enabled else None
        self.store = HostExpertStore(cfg, tparams, chaos=self.chaos)
        self.cache = ExpertCache(
            config.cache_slots, self.store.buffer_shapes(),
            jnp.dtype(cfg.dtype),
            table_shape=(self.store.num_layers, cfg.num_experts),
            chaos=self.chaos)
        mode = config.prefetch_mode if self.policy in ("spmoe", "moe-infinity") \
            else ("vanilla" if self.policy == "adapmoe" else "off")
        self.prefetcher = Prefetcher(
            self.store, self.cache, mode, config.batched_io,
            retries=config.prefetch_retries,
            backoff_s=config.retry_backoff_s,
            task_timeout_s=config.task_timeout_s,
            verify=config.resolved_verify_payloads,
            heartbeat_timeout_s=config.heartbeat_timeout_s,
            max_worker_restarts=config.max_worker_restarts,
            fail_threshold=config.fail_threshold,
            chaos=self.chaos)
        self.k = config.k_prefetch if config.k_prefetch is not None \
            else cfg.num_experts_per_tok
        self.predictor = ExpertPredictor(cfg, tparams, self.k)
        # cutoff layer from the analytical model (or explicit override)
        if config.cutoff is not None:
            self.cutoff = config.cutoff
        elif config.profile is not None:
            self.cutoff = solve_cutoff(config.profile, self.k,
                                       self.store.num_layers,
                                       max(self.draft_len, 1)).cutoff_layer
        else:
            self.cutoff = self.store.num_layers - 1
        # MoE-Infinity history is request-level: each DecodeState carries its
        # own device-resident [L, E] count array of this shape.
        self._hist_shape = (self.store.num_layers, cfg.num_experts)
        self._fast_traces = 0     # trace-time counter (retrace regression)
        self._batched_traces = 0  # ditto for the cross-session fused path
        self._build_jitted()
        # stats (engine-global plane: cumulative across every session)
        self.layer_hits = 0
        self.layer_lookups = 0
        self.on_demand_loads = 0
        self.host_syncs = 0
        self.verify_syncs = 0
        self.verify_blocks = 0
        self.fast_blocks = 0
        self.fast_fallbacks = 0
        self.iterations = 0
        self.drafted = 0
        self.accepted = 0
        # round-level accounting for the batched cross-session scheduler
        # (bench metrics, not part of counters()): verify_rounds counts
        # session_turns rounds that verified at least one block,
        # round_launches the verify dispatches those rounds needed — 1 fused
        # launch per all-hit round regardless of how many sessions it served.
        self.verify_rounds = 0
        self.round_launches = 0
        # expert bytes cache_moe's occupancy compaction gathers (counted at
        # dispatch from the kernel's own rule, so nothing syncs)
        self.compact_bytes = 0
        self._expert_bytes = self.store.expert_bytes()
        # graceful-degradation ladder: while the prefetch plane is unhealthy
        # (worker dead beyond its restart budget, wedged past its heartbeat,
        # or circuit-breaker open on failure pressure) the offload policy
        # steps down to on-demand synchronous loading — _prefetch() submits
        # nothing, the slow path's miss waves carry the load — and steps
        # back up when health returns.  Tokens are never wrong, only slower;
        # only a synchronous load that ITSELF exhausts its retry budget ends
        # the one owning request with finish_reason="io_error".
        self._degraded = False
        self.degraded_rounds = 0
        self.io_errors = 0
        # adaptive fast-path arming is per-session (DecodeState.fast_ok):
        # cold caches go straight to the slow (miss-resolving) path; a
        # zero-miss slow block re-arms, and after a misprediction
        # fast_penalty demands that many consecutive clean slow blocks
        # before re-arming.  _fast_hint is the engine-global residual: the
        # last observed arming state of the shared cache, used only to seed
        # NEWLY admitted sessions (so request 2 on a warm engine starts on
        # the fast path instead of paying per-layer syncs to rediscover
        # warmth).
        self._fast_hint = False
        self._st: Optional[DecodeState] = None   # state bound to this turn
        if config.precompile and self.policy != "adapmoe":
            self._precompile_fast()

    # ------------------------------------------------------------------ sync
    def _readback(self, x, verify: bool = False):
        """The decode path's device->host sync point: every blocking
        transfer of a turn funnels through here, so ``host_syncs`` counts
        them all.  ``verify`` marks a sync made while verifying or accepting
        a block; those alone count in ``verify_syncs``, the counter that
        carries the ≤2-per-block / ≤2-per-round contract."""
        self.host_syncs += 1
        if verify:
            self.verify_syncs += 1
        return np.asarray(x)

    # ------------------------------------------------------------------ jit
    def _build_jitted(self):
        """Jit the verify-path steps.  Every weight a step reads is an
        argument (``rp`` = the resident params below): a closed-over array
        would be baked into the executable as a constant — at published
        widths that is one private copy of the embedding, head and
        attention weights per compiled step."""
        cfg = self.cfg
        mp = self.tparams["layers"]

        def attn_half(lp, x, cache_l, pos):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            if cfg.use_mla:
                a, cache_l = L.mla_decode(lp["attn"], h, cache_l, pos, cfg)
            else:
                a, cache_l = L.attention_decode(lp["attn"], h, cache_l, pos, cfg)
            x = x + a
            h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            return x, cache_l, h2

        def gate_fn(gate_w, h2):
            w, ids, probs, _ = gate_topk(gate_w, h2.reshape(-1, cfg.d_model),
                                         cfg.num_experts_per_tok,
                                         cfg.norm_topk_prob)
            return w, ids, probs

        def cached_moe_apply(bufs, x, slot_ids, weights):
            """x: [T,d]; slot_ids/weights: [T,k] -> [T,d].  Slot-indexed
            grouped kernel over the cache pool; slot_ids < 0 contribute 0 —
            the hit/miss/wave split is pure masking, no gather on host."""
            return ops.cache_moe(x, slot_ids, weights,
                                 bufs["wu"], bufs["wd"], bufs.get("wg"))

        def shared_and_residual(lp, x, h2, y_experts):
            if cfg.num_shared_experts:
                y_experts = y_experts + ffn_forward(lp["moe"]["shared"], h2, "swiglu")
            return x + y_experts

        def dense_block(lp, x, cache_l, pos):
            x, cache_l, h2 = attn_half(lp, x, cache_l, pos)
            y = ffn_forward(lp["ffn"], h2, cfg.ffn_activation)
            return x + y, cache_l

        def embed(rp, tokens):
            return jnp.take(rp["wte"], tokens, axis=0)

        def head(rp, x):
            xf = L.rms_norm(x, rp["ln_f"], cfg.norm_eps)
            if cfg.tie_embeddings:
                return jnp.einsum("bsd,vd->bsv", xf, rp["wte"])
            return jnp.einsum("bsd,dv->bsv", xf, rp["head"])

        # per-MoE-layer params *without* the resident expert weights: the hot
        # path must only ever read experts from the cache slot buffers.
        lp_scan: Dict[str, Any] = {"ln1": mp["ln1"], "ln2": mp["ln2"],
                                   "attn": mp["attn"],
                                   "gate": mp["moe"]["gate"]}
        if cfg.num_shared_experts:
            lp_scan["shared"] = mp["moe"]["shared"]
        self._rp = {k: self.tparams[k]
                    for k in ("wte", "ln_f", "head", "dense_layers")
                    if k in self.tparams}
        self._rp["layers"] = lp_scan

        def dense_stack(dense_lp, x, dcache, pos):
            def dbody(carry, xs):
                lp, cl = xs
                xo, ncl = dense_block(lp, carry, cl, pos)
                return xo, ncl
            return jax.lax.scan(dbody, x, (dense_lp, dcache))

        def verify_fast(rp, bufs, table, history, tokens, pos, tcache):
            """Whole verify block as ONE device computation (lax.scan over
            the stacked MoE layers), speculating that every routed expert is
            cache-resident.  Returns (logits, all_hit, new_tcache,
            new_history, n_active); nothing here syncs to host."""
            self._fast_traces += 1        # trace-time side effect only
            x = embed(rp, tokens)
            T = tokens.shape[1]
            new_tcache = dict(tcache)
            if "dense_layers" in rp:
                x, new_tcache["dense_layers"] = dense_stack(
                    rp["dense_layers"], x, tcache["dense_layers"], pos)

            def mbody(carry, xs):
                x, ok, nact = carry
                lp, cl, trow = xs
                x2, ncl, h2 = attn_half(lp, x, cl, pos)
                w, ids, _ = gate_fn(lp["gate"], h2)
                slot_ids = trow[ids]                      # [T,k]; -1 = miss
                hit = slot_ids >= 0
                ok = jnp.logical_and(ok, jnp.all(hit))
                y = cached_moe_apply(bufs, h2.reshape(T, cfg.d_model),
                                     slot_ids, jnp.where(hit, w, 0.0))
                y3 = y.reshape(1, T, cfg.d_model)
                if cfg.num_shared_experts:
                    y3 = y3 + ffn_forward(lp["shared"], h2, "swiglu")
                activated = jnp.zeros((cfg.num_experts,), jnp.int32
                                      ).at[ids.reshape(-1)].add(1) > 0
                nact = nact + jnp.sum(activated.astype(jnp.float32))
                return (x2 + y3, ok, nact), (ncl, activated)

            (x, ok, nact), (nlayers, act) = jax.lax.scan(
                mbody, (x, jnp.bool_(True), jnp.float32(0.0)),
                (rp["layers"], tcache["layers"], table))
            new_tcache["layers"] = nlayers
            new_history = history + act.astype(history.dtype)
            return head(rp, x), ok, new_tcache, new_history, nact

        def verify_fast_batched(rp, bufs, table, hists, tokens, pos, tcaches):
            """Whole scheduling ROUND as one device computation: every ready
            session's verify block in a single fused dispatch.  ``tokens`` is
            a tuple of [1, T_i] blocks (T_i may be ragged), ``hists`` /
            ``pos`` / ``tcaches`` the matching per-session state tuples.

            Attention stays per-session (each has its own KV cache and
            position — identical shapes and ops to the solo fast path, so
            per-session results are bit-identical to serving it alone), while
            everything row-wise is concatenated into one [ΣT_i, ·] batch:
            ONE routing pass, ONE ``table_dev`` gather, ONE ``cache_moe``
            launch and ONE head projection per layer-scan, instead of one
            each per session.  Row-wise ops are bit-stable under
            concatenation (each row's reduction order is independent of the
            batch), which is what makes batched rounds lossless.

            Returns (logits [1, ΣT, V], ok [N] per-session all-hit flags,
            new_tcaches, new_hists, nact [N]); nothing here syncs to host.
            A session that misses falls back alone — the caller commits its
            batchmates' results and re-runs only that session's block on the
            slow path."""
            self._batched_traces += 1     # trace-time side effect only
            n = len(tokens)
            Ts = tuple(int(t.shape[1]) for t in tokens)
            offs = [0]
            for t in Ts:
                offs.append(offs[-1] + t)
            new_tcaches = [dict(tc) for tc in tcaches]
            xs = []
            for i in range(n):
                x = embed(rp, tokens[i])
                if "dense_layers" in rp:
                    x, new_tcaches[i]["dense_layers"] = dense_stack(
                        rp["dense_layers"], x, tcaches[i]["dense_layers"],
                        pos[i])
                xs.append(x)

            def mbody(carry, scan_xs):
                xs_c, ok_c, nact_c = carry
                lp, cls, trow = scan_xs
                x2s, ncls, h2s = [], [], []
                for i in range(n):
                    x2, ncl, h2 = attn_half(lp, xs_c[i], cls[i], pos[i])
                    x2s.append(x2)
                    ncls.append(ncl)
                    h2s.append(h2)
                h2cat = jnp.concatenate(
                    [h2s[i].reshape(Ts[i], cfg.d_model) for i in range(n)])
                w, ids, _ = gate_fn(lp["gate"], h2cat)    # ONE routing pass
                slot_ids = trow[ids]                      # [ΣT, k]; -1 = miss
                hit = slot_ids >= 0
                ycat = cached_moe_apply(bufs, h2cat, slot_ids,
                                        jnp.where(hit, w, 0.0))
                outs, oks, nacts, acts = [], [], [], []
                for i in range(n):
                    r0, r1 = offs[i], offs[i + 1]
                    y3 = ycat[r0:r1].reshape(1, Ts[i], cfg.d_model)
                    if cfg.num_shared_experts:
                        y3 = y3 + ffn_forward(lp["shared"], h2s[i], "swiglu")
                    outs.append(x2s[i] + y3)
                    oks.append(jnp.logical_and(ok_c[i],
                                               jnp.all(hit[r0:r1])))
                    activated = jnp.zeros((cfg.num_experts,), jnp.int32
                                          ).at[ids[r0:r1].reshape(-1)
                                               ].add(1) > 0
                    acts.append(activated)
                    nacts.append(nact_c[i] +
                                 jnp.sum(activated.astype(jnp.float32)))
                return (tuple(outs), tuple(oks), tuple(nacts)), \
                    (tuple(ncls), tuple(acts))

            carry0 = (tuple(xs),
                      tuple(jnp.bool_(True) for _ in range(n)),
                      tuple(jnp.float32(0.0) for _ in range(n)))
            (xs_f, ok_f, nact_f), (nlayers, acts) = jax.lax.scan(
                mbody, carry0,
                (rp["layers"], tuple(tc["layers"] for tc in tcaches), table))
            for i in range(n):
                new_tcaches[i]["layers"] = nlayers[i]
            new_hists = tuple(hists[i] + acts[i].astype(hists[i].dtype)
                              for i in range(n))
            xcat = jnp.concatenate(xs_f, axis=1)          # [1, ΣT, d]
            return (head(rp, xcat), jnp.stack(ok_f), tuple(new_tcaches),
                    new_hists, jnp.stack(nact_f))

        self._attn_half = jax.jit(attn_half)
        self._gate = jax.jit(gate_fn)
        self._moe_apply = jax.jit(cached_moe_apply)
        self._shared_res = jax.jit(shared_and_residual)
        self._dense_stack = jax.jit(dense_stack)
        self._embed = jax.jit(embed)
        self._head = jax.jit(head)
        self._verify_fast = jax.jit(verify_fast)
        self._verify_fast_batched = jax.jit(verify_fast_batched)
        # fixed-shape masked row add: one executable regardless of how many
        # experts a layer activated (a [E]-gather scatter would retrace per
        # distinct unique-count)
        self._hist_add = jax.jit(lambda h, l, mask: h.at[l].add(mask))
        self._draft_step = (jax.jit(functools.partial(
            self.draft.decode_step, collect_taps=True))
            if self.draft is not None else None)

    def _precompile_fast(self):
        """Trace + compile ``_verify_fast`` for every decode block shape this
        config can produce, so no armed fast block ever holds the cache lock
        across a trace (ROADMAP open item).  ``sd`` / ``greedy`` have one
        block shape ([1, N+1]); ``sd-adaptive`` pre-traces the whole
        draft-length ladder [min_draft_len, max_draft_len] — previously only
        ``min_draft_len + 1`` was compiled and every distinct adapted length
        retraced mid-serve.  The dummy calls' inputs mirror the decode-time
        signature exactly — int32 tokens, a python-int position, the
        session-shaped KV cache — so the jit cache entries are the ones
        ``_verify_block`` hits (regressions:
        tests/test_engine.py::test_no_retrace_on_second_fast_block,
        tests/test_sessions.py::test_adaptive_ladder_precompiled)."""
        if self.decode == DecodePolicy.SD_ADAPTIVE.value:
            lens = range(self.config.min_draft_len,
                         self.config.max_draft_len + 1)
        else:
            lens = (self.draft_len,)
        tcache = self.target.init_cache(1, self.max_seq)
        bufs, table = self.cache.snapshot()   # init: nothing inserts yet
        hist = jnp.zeros(self._hist_shape, jnp.float32)
        for n in lens:
            tokens = jnp.zeros((1, n + 1), jnp.int32)
            self._verify_fast(self._rp, bufs, table, hist, tokens, 0, tcache)

    def _layer_params(self, l: int):
        """Per-layer param slice for the slow path — attention + norms +
        gate (+ shared experts), explicitly NOT the resident expert weights."""
        mp = self.tparams["layers"]
        moe_small: Dict[str, Any] = {"gate": mp["moe"]["gate"][l]}
        if self.cfg.num_shared_experts:
            moe_small["shared"] = jax.tree.map(lambda a: a[l],
                                               mp["moe"]["shared"])
        return {"ln1": jax.tree.map(lambda a: a[l], mp["ln1"]),
                "ln2": jax.tree.map(lambda a: a[l], mp["ln2"]),
                "attn": jax.tree.map(lambda a: a[l], mp["attn"]),
                "moe": moe_small}

    # ------------------------------------------------------------- verification
    def _count_compaction(self, st: DecodeState, rows: int, calls: int = 1):
        """Credit ``st`` with the expert weights that ``calls`` dispatched
        ``cache_moe`` calls over ``rows`` tokens each gather when the pool
        is larger than the slots those tokens can occupy
        (``kernels/cache_moe.compacted_slots``)."""
        n = calls * compacted_slots(rows, self.cfg.num_experts_per_tok,
                                    self.cache.num_slots) * self._expert_bytes
        self.compact_bytes += n
        st.io["compact_bytes"] += n

    def _ensure_loaded(self, layer: int, ids: np.ndarray
                       ) -> Tuple[Dict[ExpertKey, int], List[ExpertKey]]:
        keys = [(layer, int(e)) for e in dict.fromkeys(ids.ravel().tolist())]
        hits, misses = self.cache.lookup(keys)
        self.layer_lookups += len(keys)
        self.layer_hits += len(hits)
        return hits, misses

    # -------------------------------------------------------------- resilience
    def _check_health(self):
        """One degradation-ladder step, run once per scheduling round:
        probe-and-repair the prefetch plane (restart a dead worker within
        budget, release stranded tasks past it) and step the offload policy
        down to on-demand synchronous loading while the plane is unhealthy.
        Health returning steps back up automatically — ``_degraded`` is
        recomputed every round, never latched."""
        if self.prefetcher.mode == "off":
            self._degraded = False
        else:
            self._degraded = not self.prefetcher.revive()

    def health(self) -> str:
        """Ladder position: ``"healthy"`` (prefetch plane trusted),
        ``"degraded"`` (on-demand synchronous loads; expected to recover),
        or ``"failed"`` (worker permanently gone — restart budget spent)."""
        if not self._degraded:
            return "healthy"
        pf = self.prefetcher
        if pf.mode == "worker" and not pf.worker_alive() \
                and pf.worker_restarts >= pf.max_worker_restarts:
            return "failed"
        return "degraded"

    def _load_wave(self, wave: List[ExpertKey], st: DecodeState) -> List[int]:
        """Decode-critical on-demand load: fetch + insert one miss wave
        under a bounded retry budget (``io_retries``), with checksum
        verification when enabled.  The FINAL attempt runs inside the chaos
        injector's ``calm()`` scope, so *injected* faults can never exhaust
        this budget — losslessness under chaos is a guarantee.  A real
        fault that survives every retry raises :class:`ExpertLoadError`:
        the degradation ladder's last rung, ending the one owning request
        with ``finish_reason="io_error"`` (never wrong tokens)."""
        attempts = self.config.io_retries + 1
        verify = self.prefetcher.verify
        last: Optional[BaseException] = None
        with jax.profiler.TraceAnnotation("spmoe.load_wave",
                                          request_id=st.request_id):
            for a in range(attempts):
                calm = self.chaos.calm() if self.chaos is not None \
                    and a == attempts - 1 else contextlib.nullcontext()
                try:
                    with calm:
                        arrays = self.store.fetch_verified(wave) if verify \
                            else self.store.fetch(wave)
                        return self.cache.insert(wave, arrays, mark_used=True,
                                                 stats=st.io)
                except OSError as e:       # ChaosError/PayloadCorruption too
                    last = e
                    if a < attempts - 1:
                        time.sleep(self.config.retry_backoff_s * (2 ** a))
        self.io_errors += 1
        raise ExpertLoadError(
            f"on-demand load of {len(wave)} experts failed after "
            f"{attempts} attempts: {last}") from last

    def _verify_block(self, tokens: jax.Array, pos: int, tcache):
        """Layer-wise target forward with cache-aware expert compute.
        tokens: [1, N+1].  See module docstring for the fast/slow design.
        Session state (fast-path arming, history, hit accumulator) is read
        from ``self._st`` — bound by the turn that dispatched this block —
        so the signature stays the sync-spy hook tests wrap."""
        st = self._st
        self.verify_blocks += 1
        if st.fast_ok and self.policy != "adapmoe":
            # snapshot + dispatch under the cache lock: a concurrent donating
            # insert must not delete the buffer handle mid-dispatch.
            with self.cache.lock:
                bufs, table = self.cache.snapshot()
                logits, ok, ncache, nhist, nact = self._verify_fast(
                    self._rp, bufs, table, st.history_dev, tokens, pos,
                    tcache)
            self._count_compaction(st, tokens.shape[1],
                                   self.store.num_layers)
            if bool(self._readback(ok, verify=True)):   # 1 of ≤2 a block
                st.history_dev = nhist
                st.fast_active_dev = st.fast_active_dev + nact
                st.fast_blocks += 1
                self.fast_blocks += 1
                return logits, ncache
            st.fast_ok = False                    # mispredicted availability
            st.fast_penalty = 2
            self._fast_hint = False
            self.fast_fallbacks += 1
        return self._verify_block_slow(tokens, pos, tcache)

    def _verify_block_slow(self, tokens: jax.Array, pos: int, tcache):
        """Miss-resolution path: per-layer loop, one routing readback per MoE
        layer, on-demand wave loading; re-arms the session's fast path when
        the whole block resolved from cache."""
        st = self._st
        cfg = self.cfg
        x = self._embed(self._rp, tokens)
        T = tokens.shape[1]
        total_misses = 0
        if "dense_layers" in self._rp:
            x, tcache["dense_layers"] = self._dense_stack(
                self._rp["dense_layers"], x, tcache["dense_layers"], pos)
        new_layers = []
        for l in range(self.store.num_layers):
            lp = self._layer_params(l)
            cl = jax.tree.map(lambda a: a[l], tcache["layers"])
            x, ncl, h2 = self._attn_half(lp, x, cl, pos)
            new_layers.append(ncl)
            w, ids, probs = self._gate(lp["moe"]["gate"], h2)
            ids_np = self._readback(ids, verify=True)   # miss resolution
            act = np.zeros((cfg.num_experts,), np.float32)
            act[np.unique(ids_np)] = 1.0
            st.history_dev = self._hist_add(st.history_dev, l,
                                            jnp.asarray(act))
            # AdapMoE baseline: predict next layer from *this* layer's gate
            # input using the target's own gates, synchronous prefetch.
            if self.policy == "adapmoe" and l + 1 < self.store.num_layers:
                nxt = self.predictor.predict_layer(
                    l + 1, h2[:, -1:],
                    functools.partial(self._readback, verify=True))
                _, miss = self.cache.lookup(nxt, touch=False)
                if miss:
                    self._prefetch(st, miss)         # vanilla mode: blocking
            xf = h2.reshape(T, cfg.d_model)
            # lookup through dispatch under the cache lock: a prefetch insert
            # in between could evict a hit and refill its slot
            with self.cache.lock:
                hits, misses = self._ensure_loaded(l, ids_np)
                # cached-first compute (dispatches async under jax): hit
                # experts' slots unmasked, everything else -1
                slot_lut = np.full((cfg.num_experts,), -1, np.int64)
                for (_, e), s in hits.items():
                    slot_lut[e] = s
                bufs, _ = self.cache.snapshot()
                y = self._moe_apply(bufs, xf,
                                    jnp.asarray(slot_lut[ids_np], jnp.int32), w)
            self._count_compaction(st, T)
            total_misses += len(misses)
            if misses:
                # on-demand batched loads, in cache-capacity-bounded waves:
                # each wave's experts are loaded (evicting as needed — the
                # hit experts' compute is already dispatched) and its share
                # of the block is computed before the next wave streams in.
                self.on_demand_loads += len(misses)
                wave_size = max(1, self.cache.num_slots)
                for w0 in range(0, len(misses), wave_size):
                    wave = misses[w0:w0 + wave_size]
                    # insert through dispatch under the cache lock, so a
                    # prefetch insert cannot evict the wave first (the
                    # worker's own fetches go on meanwhile)
                    with self.cache.lock:
                        slots = self._load_wave(wave, st)
                        wave_lut = np.full((cfg.num_experts,), -1, np.int64)
                        for (key, s) in zip(wave, slots):
                            wave_lut[key[1]] = s
                        bufs, _ = self.cache.snapshot()
                        y = y + self._moe_apply(
                            bufs, xf,
                            jnp.asarray(wave_lut[ids_np], jnp.int32), w)
                    self._count_compaction(st, T)
            x = self._shared_res(lp, x, h2, y.reshape(1, T, cfg.d_model))
        tcache["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *new_layers)
        if self.policy != "adapmoe":
            if total_misses == 0:
                if st.fast_penalty > 0:
                    st.fast_penalty -= 1
                st.fast_ok = st.fast_penalty == 0
            else:
                st.fast_ok = False
            self._fast_hint = st.fast_ok   # seed arming of future sessions
        return self._head(self._rp, x), tcache

    # ------------------------------------------------------------ session API
    # The turn-based serving surface: a scheduler (core/engine.py's
    # Engine.serve round-robin, or the generate_stream wrapper below for a
    # single session) admits a session with start_session, advances it one
    # committed verify block at a time with session_turn, and retires it
    # with finish_session.  All three re-bind self._st, so any number of
    # sessions may interleave turns on the one warm cache.

    def start_session(self, prompt: jax.Array, max_new_tokens: int,
                      request_id: Optional[str] = None) -> DecodeState:
        """Admit one request: allocate its per-session plane (KV cache,
        draft cache, request-level history, fast-path arming seeded from the
        engine's warm hint) and run the prefill verify block — through the
        cache-aware path, so its expert loads warm the shared cache.
        ``request_id`` only labels the session's spans."""
        assert prompt.shape[0] == 1
        st = DecodeState(
            max_new=max_new_tokens,
            tcache=self.target.init_cache(1, self.max_seq),
            request_id=request_id,
            n=self.draft_len,                     # 0 for greedy decode
            history_dev=jnp.zeros(self._hist_shape, jnp.float32),
            fast_active_dev=jnp.zeros((), jnp.float32),
            fast_ok=self._fast_hint and self.policy != "adapmoe")
        if max_new_tokens <= 0:
            st.finished = True
            return st
        self._st = st
        with jax.profiler.TraceAnnotation("spmoe.prefill",
                                          request_id=request_id):
            if st.n > 0:
                _, st.dcache = self.draft.prefill(self.dparams, prompt,
                                                  self.max_seq)
            logits, st.tcache = self._verify_block(prompt, 0, st.tcache)
            st.cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            st.pending = [int(self._readback(st.cur, verify=True)[0, 0])]
        st.pos = prompt.shape[1]
        st.emitted_total = 1
        return st

    # sentinel: _turn_early found nothing to deliver — the turn must draft
    # and verify (None is a real return value, "session done")
    _NEEDS_VERIFY = object()

    def _turn_early(self, st: DecodeState):
        """The no-verify turn outcomes: session already done (None), prefill
        chunk awaiting delivery (the chunk), or token budget exhausted
        (None).  Returns ``_NEEDS_VERIFY`` when a verify block is due."""
        if st.finished:
            return None
        if st.pending is not None:             # deliver the prefill token
            chunk, st.pending = st.pending, None
            st.finished = st.emitted_total >= st.max_new
            return chunk
        if st.emitted_total >= st.max_new:
            st.finished = True
            return None
        return self._NEEDS_VERIFY

    def _turn_draft(self, st: DecodeState
                    ) -> Tuple[List[int], jax.Array]:
        """Prefetch-signal + drafting stage of one turn: MoE-Infinity
        history prefetch, the draft loop with SP-MoE speculative prefetching,
        and the assembled verify block.  Returns (drafts, block [1, N+1])."""
        N = st.n
        drafts = []
        with jax.profiler.TraceAnnotation("spmoe.draft",
                                          request_id=st.request_id):
            # MoE-Infinity: request-level historical prefetch, all layers
            if self.policy == "moe-infinity":
                hist = self._readback(st.history_dev)
                for l in range(self.store.num_layers):
                    top = np.argsort(-hist[l])[: self.k]
                    keys = [(l, int(e)) for e in top]
                    # while the fast verify path is armed it never touches
                    # the LRU itself (that would need a device readback), so
                    # predicted-hot experts carry the recency signal instead
                    _, miss = self.cache.lookup(keys, touch=st.fast_ok)
                    if miss:
                        self._prefetch(st, miss)
            # ---- drafting stage (+ SP-MoE speculative prefetching) ----
            tok = st.cur
            for i in range(N):
                lg, st.dcache, taps = self._draft_step(
                    self.dparams, st.dcache, tok, jnp.int32(st.pos + i))
                tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
                drafts.append(int(self._readback(tok)[0, 0]))
                if self.policy == "spmoe" and self.cutoff >= 0:
                    with jax.profiler.TraceAnnotation(
                            "spmoe.predict", request_id=st.request_id):
                        self._predict_and_prefetch(st, taps)
        block = jnp.concatenate(
            [st.cur, jnp.asarray([drafts], jnp.int32)], axis=1) \
            if drafts else st.cur
        return drafts, block

    def _predict_and_prefetch(self, st: DecodeState, taps):
        """SP-MoE's prediction for one draft token: each target MoE layer
        up to the cutoff scores the draft's tap through its own router,
        looks the predicted experts up, and submits the misses."""
        tap_stack = self._draft_taps_for_moe(taps)
        for l in range(min(self.cutoff + 1, self.store.num_layers)):
            keys = self.predictor.predict_layer(l, tap_stack[l],
                                                self._readback)
            # see moe-infinity note: predictions substitute for LRU touches
            # while the fast path is armed
            _, miss = self.cache.lookup(keys, touch=st.fast_ok)
            if miss:
                self._prefetch(st, miss)

    def _turn_commit(self, st: DecodeState, drafts: List[int],
                     greedy: np.ndarray) -> List[int]:
        """Accept/commit stage: greedy is the verified block's argmax row
        ([N+1] host ints).  Identical whether the block was verified solo or
        as a slice of a batched cross-session round."""
        cfg = self.config
        N = len(drafts)
        d = np.asarray(drafts, np.int64)
        match = d == greedy[:N]
        n_acc = int(np.cumprod(match.astype(np.int64)).sum())
        emitted = [int(t) for t in d[:n_acc]] + [int(greedy[n_acc])]
        st.cur = jnp.asarray([[int(greedy[n_acc])]], jnp.int32)
        st.pos += n_acc + 1
        self.iterations += 1
        self.drafted += N
        self.accepted += n_acc
        if self.decode == DecodePolicy.SD_ADAPTIVE.value:
            st.n, st.acc_ewma = S.adaptive_next_len(
                N, n_acc, st.acc_ewma, cfg.min_draft_len,
                cfg.max_draft_len, cfg.draft_ewma)
        chunk = emitted[:st.max_new - st.emitted_total]
        st.emitted_total += len(chunk)
        st.finished = st.emitted_total >= st.max_new
        return chunk

    def session_turn(self, st: DecodeState) -> Optional[List[int]]:
        """Advance one session by ONE committed chunk; returns the chunk
        (clipped to the max_new_tokens budget) or None once the session has
        nothing left to emit.  The block schedule is decode-policy-aware:
        greedy = a 1-token block with no drafting stage, sd = a fixed-N
        draft-then-verify block, sd-adaptive = the EWMA controller of
        core/sd.py driving this session's own draft length."""
        early = self._turn_early(st)
        if early is not self._NEEDS_VERIFY:
            return early
        self._check_health()                 # one ladder step per turn
        if self._degraded:
            self.degraded_rounds += 1
        self._st = st
        drafts, block = self._turn_draft(st)
        with jax.profiler.TraceAnnotation("spmoe.verify",
                                          request_id=st.request_id):
            try:
                tlogits, st.tcache = self._verify_block(block, st.pos,
                                                        st.tcache)
            except ExpertLoadError:
                # the ladder's last rung: this session cannot make progress
                # without the failed load — end it (the caller maps this to
                # finish_reason="io_error"); batchmates are unaffected.
                st.finished = True
                raise
            greedy = self._readback(jnp.argmax(tlogits, -1),
                                    verify=True)[0]               # accept
            return self._turn_commit(st, drafts, greedy)

    def _counter_delta(self, before: Dict[str, int]) -> Dict[str, int]:
        after = self.counters()
        return {k: after[k] - before[k] for k in _COUNTER_KEYS}

    @staticmethod
    def _merge_delta(into: Dict[str, int], delta: Dict[str, int]):
        for k, v in delta.items():
            into[k] = into.get(k, 0) + v

    def session_turns(self, sts: Sequence[DecodeState]
                      ) -> List[Tuple[Optional[List[int]], Dict[str, int]]]:
        """Advance SEVERAL sessions by one committed verify block each in a
        single scheduling round, verifying the ready sessions' blocks with
        ONE fused fast-path dispatch (``_verify_fast_batched``): one routing
        pass, one ``table_dev`` gather, one ``cache_moe`` launch, and ≤2
        verify syncs for the whole round — the per-session all-hit vector
        and the accept/reject argmax, each read back once — instead of 2·N.

        Per-session drafting (and its prefetch submissions) stays
        sequential ahead of the fused verify; sessions whose fast path is
        not armed (cold cache, post-misprediction penalty, adapmoe) verify
        solo on their usual path, and a batched session whose block missed
        falls back ALONE to the slow miss-resolving path — its batchmates'
        fused results commit untouched.  Per-session results are
        bit-identical to ``session_turn`` serving each session by itself.

        Returns one ``(chunk, counter_delta)`` pair per session:
        ``counter_delta`` attributes this round's cumulative-counter growth
        to the session that caused it (the ≤2 shared round syncs are charged
        to the round's first fused session so the per-request ledgers still
        tile the cumulative counters exactly)."""
        # a chunk is List[int], None (session done), or an ExpertLoadError
        # instance (session ended by the ladder's io_error rung)
        chunks: List[Any] = [None] * len(sts)
        deltas: List[Dict[str, int]] = [{} for _ in sts]
        pend: List[Tuple[int, DecodeState, List[int], jax.Array]] = []
        self._check_health()                 # one ladder step per round
        degraded_counted = False
        for i, st in enumerate(sts):
            before = self.counters()
            early = self._turn_early(st)
            if early is not self._NEEDS_VERIFY:
                chunks[i] = early
                deltas[i] = self._counter_delta(before)
                continue
            self._st = st
            if self._degraded and not degraded_counted:
                # charge the round's one degraded tick to the round's first
                # verifying session, INSIDE its delta window, so the
                # per-request ledgers still tile the cumulative counter
                degraded_counted = True
                self.degraded_rounds += 1
            drafts, block = self._turn_draft(st)
            deltas[i] = self._counter_delta(before)
            pend.append((i, st, drafts, block))
        if pend:
            self.verify_rounds += 1
        fused = [p for p in pend
                 if p[1].fast_ok and self.policy != "adapmoe"]
        if len(fused) >= 2:
            # canonical order: sort by block length (stable, so ties keep
            # admission order) — (4,6) and (6,4) rounds then share ONE
            # fused-trace signature instead of retracing per permutation of
            # sd-adaptive's ragged lengths.  Concat order is transparent to
            # each session's results (row-stable ops), so this is lossless.
            fused.sort(key=lambda p: p[3].shape[1])
            fused_idx = {p[0] for p in fused}
            solo = [p for p in pend if p[0] not in fused_idx]
            self._round_fused(fused, chunks, deltas)
        else:
            solo = pend
        for i, st, drafts, block in solo:
            before = self.counters()
            self._st = st
            self.round_launches += 1
            with jax.profiler.TraceAnnotation("spmoe.verify",
                                              request_id=st.request_id):
                try:
                    tlogits, st.tcache = self._verify_block(block, st.pos,
                                                            st.tcache)
                    greedy = self._readback(jnp.argmax(tlogits, -1),
                                            verify=True)[0]
                    chunks[i] = self._turn_commit(st, drafts, greedy)
                except ExpertLoadError as e:
                    # ladder's last rung: end ONLY this session — batchmates'
                    # turns proceed.  The scheduler maps the exception chunk
                    # to finish_reason="io_error" (see engine.Session.deliver)
                    st.finished = True
                    chunks[i] = e
            self._merge_delta(deltas[i], self._counter_delta(before))
        return list(zip(chunks, deltas))

    def _round_fused(self, fused, chunks, deltas):
        """The fused leg of one scheduling round: dispatch every armed
        session's block in one ``_verify_fast_batched`` call, read the
        per-session all-hit vector and the round's accept/reject argmax back
        once each, then commit hits / re-run misses per session."""
        idxs = [p[0] for p in fused]
        sts = [p[1] for p in fused]
        blocks = [p[3] for p in fused]
        offs = [0]
        for b in blocks:
            offs.append(offs[-1] + b.shape[1])
        self.round_launches += 1
        with jax.profiler.TraceAnnotation("spmoe.verify_round"):
            # snapshot + dispatch under the cache lock: a concurrent donating
            # insert must not delete the buffer handle mid-dispatch.
            with self.cache.lock:
                bufs, table = self.cache.snapshot()
                logits, ok_vec, new_tcaches, new_hists, nact_vec = \
                    self._verify_fast_batched(
                        self._rp, bufs, table,
                        tuple(st.history_dev for st in sts),
                        tuple(blocks),
                        tuple(st.pos for st in sts),
                        tuple(st.tcache for st in sts))
            # the round's one compaction per layer is charged to its first
            # session, as its two syncs are
            self._count_compaction(sts[0], offs[-1], self.store.num_layers)
            ok = self._readback(ok_vec, verify=True)        # round sync 1
            greedy = self._readback(jnp.argmax(logits, -1),
                                    verify=True)[0]         # round sync 2
            for k in ("host_syncs", "verify_syncs"):
                deltas[idxs[0]][k] = deltas[idxs[0]].get(k, 0) + 2
            for j, (i, st, drafts, _) in enumerate(fused):
                before = self.counters()
                self._st = st
                self.verify_blocks += 1
                if bool(ok[j]):
                    st.history_dev = new_hists[j]
                    st.fast_active_dev = st.fast_active_dev + nact_vec[j]
                    st.fast_blocks += 1
                    self.fast_blocks += 1
                    st.tcache = new_tcaches[j]
                    chunks[i] = self._turn_commit(
                        st, drafts, greedy[offs[j]:offs[j + 1]])
                else:
                    # mispredicted availability: this session falls back
                    # alone; its speculative tcache/history copies are
                    # discarded
                    st.fast_ok = False
                    st.fast_penalty = 2
                    self._fast_hint = False
                    self.fast_fallbacks += 1
                    self.round_launches += 1
                    try:
                        tlogits, st.tcache = self._verify_block_slow(
                            blocks[j], st.pos, st.tcache)
                        g = self._readback(jnp.argmax(tlogits, -1),
                                           verify=True)[0]
                        chunks[i] = self._turn_commit(st, drafts, g)
                    except ExpertLoadError as e:
                        # end ONLY this session; its fused batchmates
                        # committed
                        st.finished = True
                        chunks[i] = e
                self._merge_delta(deltas[i], self._counter_delta(before))

    def _prefetch(self, st: DecodeState, keys):
        """Submit a prefetch on behalf of ``st``, remembering the task so
        retirement waits on exactly this session's in-flight I/O.  While the
        ladder is degraded the prefetch plane is not trusted: submit nothing
        and let the slow path's on-demand waves carry the load."""
        if self._degraded:
            return
        task = self.prefetcher.submit(keys)
        if task is not None:
            st.inflight.append(task)

    def finish_session(self, st: DecodeState):
        """Retire a session (idempotent, runs on every exit path): commit
        its device-side fast-path hit accumulator into the cumulative
        lookup/hit counters — the ONE metrics-plane readback per session,
        off the decode path, hence deliberately not routed through
        ``_readback`` — and wait out the session's OWN prefetch tasks so
        none is in flight against a retired request's predictions.  Only
        this session's tasks: a full ``prefetcher.drain()`` here would
        stall still-active concurrent sessions on the shared worker at
        every retirement boundary."""
        if st.committed:
            return
        st.committed = True
        st.finished = True
        if st.fast_blocks:
            fast_active = int(np.asarray(st.fast_active_dev))
            self.layer_lookups += fast_active
            self.layer_hits += fast_active
        for task in st.inflight:       # worker sets done even on task error
            # bounded wait that pumps the prefetcher's probe-and-repair
            # (revive / abandon_pending), so a dead-and-unrestartable worker
            # can never strand retirement on a task nobody will ever run
            if self.prefetcher.wait_task(
                    task, timeout=self.config.drain_timeout_s):
                for k, v in task.stats.items():  # owner-attributed I/O: the
                    st.io[k] = st.io.get(k, 0) + v  # task is THIS session's
        st.inflight.clear()
        self.cache.wait()              # dispatched H2D transfers have landed

    # ---------------------------------------------------------------- generate
    def generate_stream(self, prompt: jax.Array, max_new_tokens: int
                        ) -> Iterator[List[int]]:
        """Single-session streaming wrapper over the session API: yields one
        List[int] chunk per committed verify block.  Cumulative engine
        counters update per turn, so an early generator close (stop token,
        abandoned consumer) leaves consistent stats; the session is retired
        (fast-hit commit + wait on its own prefetch tasks) on every exit
        path."""
        if max_new_tokens <= 0:
            return
        st = self.start_session(prompt, max_new_tokens)
        try:
            while True:
                chunk = self.session_turn(st)
                if chunk is None:
                    return
                yield chunk
        finally:
            self.finish_session(st)

    def generate(self, prompt: jax.Array, max_new_tokens: int
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One-shot compat wrapper over ``generate_stream`` returning the
        legacy (tokens, stats-dict) shape; stats are this call's counter
        deltas (identical to the old cumulative dict on a fresh engine)."""
        before = self.counters()
        out: List[int] = []
        for chunk in self.generate_stream(prompt, max_new_tokens):
            out.extend(chunk)
        d = self._counter_delta(before)
        stats = {
            "iterations": d["iterations"],
            "acceptance_rate": d["accepted"] / max(d["drafted"], 1),
            "hit_rate": d["hits"] / max(d["lookups"], 1),
            "on_demand_loads": d["on_demand_loads"],
            "prefetched": d["prefetched"],
            "evictions": d["evictions"],
            "prefetch_evicted_unused": d["prefetch_evicted_unused"],
            "cutoff_layer": self.cutoff,
            "host_syncs": d["host_syncs"],
            "verify_syncs": d["verify_syncs"],
            "h2d_bytes": d["h2d_bytes"],
            "verify_blocks": d["verify_blocks"],
            "fast_blocks": d["fast_blocks"],
            "fast_fallbacks": d["fast_fallbacks"],
        }
        return jnp.asarray(out, jnp.int32), stats

    def counters(self) -> Dict[str, int]:
        """Raw cumulative counters (metrics plane) — host-only, never blocks
        on the device, so schedulers can snapshot it around every session
        turn for per-request delta ledgers.  The fast path counts its hits
        in a per-session device accumulator that ``finish_session`` folds
        into ``layer_lookups``/``layer_hits`` (one readback per session, at
        retirement, off the decode path)."""
        return {
            "lookups": self.layer_lookups,
            "hits": self.layer_hits,
            "on_demand_loads": self.on_demand_loads,
            "prefetched": self.prefetcher.loaded_count,
            "evictions": self.cache.evictions,
            "prefetch_evicted_unused": self.cache.prefetch_evicted,
            "h2d_bytes": self.cache.h2d_bytes,
            "compact_bytes": self.compact_bytes,
            "host_syncs": self.host_syncs,
            "verify_syncs": self.verify_syncs,
            "verify_blocks": self.verify_blocks,
            "fast_blocks": self.fast_blocks,
            "fast_fallbacks": self.fast_fallbacks,
            "iterations": self.iterations,
            "drafted": self.drafted,
            "accepted": self.accepted,
            # resilience plane (chaos-hardened serving)
            "prefetch_errors": self.prefetcher.error_count,
            "prefetch_retries": self.prefetcher.retry_count,
            "checksum_failures": self.store.checksum_failures,
            "worker_restarts": self.prefetcher.worker_restarts,
            "degraded_rounds": self.degraded_rounds,
            "io_errors": self.io_errors,
        }

    def _draft_taps_for_moe(self, taps: Dict[str, jax.Array]) -> jax.Array:
        """Map draft-layer taps onto target MoE layers (layer-to-layer
        correspondence; Table 1 pairs share num_layers)."""
        stack = taps.get("layers")
        if stack is None:
            stack = list(taps.values())[0]
        n = self.store.num_layers
        off = self.cfg.first_dense_layers
        # draft layer (l + off) predicts target moe layer l
        if stack.shape[0] >= n + off:
            return stack[off:off + n]
        return stack[:n]

    def reset_stats(self):
        """Zero the cumulative counters (cache + prefetcher + engine) so a
        warmed engine can report clean steady-state numbers."""
        self.layer_hits = self.layer_lookups = 0
        self.on_demand_loads = self.host_syncs = self.verify_syncs = 0
        self.verify_blocks = self.fast_blocks = self.fast_fallbacks = 0
        self.iterations = self.drafted = self.accepted = 0
        self.verify_rounds = self.round_launches = 0
        self.degraded_rounds = self.io_errors = 0
        self.compact_bytes = 0
        self.store.checksum_failures = 0
        self.cache.reset_stats()
        self.prefetcher.reset_stats()

    def close(self):
        self.prefetcher.stop()
