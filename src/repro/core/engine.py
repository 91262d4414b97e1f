"""Unified request-level serving facade (the public SP-MoE API).

The paper-experiment entry points (``greedy_generate`` / ``sd_generate`` /
``sd_generate_adaptive`` in ``core/sd.py``, ``OffloadEngine.generate`` in
``core/runtime.py``) remain as the *internal* layer; this module is the one
shape every caller goes through:

Two-axis policy model
---------------------
Serving behaviour decomposes into two orthogonal choices:

* ``DecodePolicy`` — *how tokens are proposed and committed*:
  ``greedy`` (plain autoregressive), ``sd`` (fixed-length speculative
  decoding), ``sd-adaptive`` (acceptance-EWMA-controlled draft length).
* ``OffloadPolicy`` — *where expert weights live and how they move*:
  ``none`` (all weights resident), ``spmoe`` (drafting-stage cross-model
  prefetch, paper Algorithm 1/2), ``adapmoe`` / ``moe-infinity`` /
  ``on-demand`` (the paper's baselines).

Every decode × offload combination is lossless: the emitted stream is
bit-identical to target-only greedy decoding.  Note ``greedy × spmoe``
degenerates to on-demand loading — SP-MoE's prefetch signal *is* the
drafting stage, so without drafts there is nothing to predict from.

Request lifecycle
-----------------
A long-lived :class:`Engine` serves a stream of :class:`Request` objects
against ONE warm :class:`~repro.core.cache.ExpertCache`, one prefetcher and
one set of compiled step functions; everything a single request mutates
lives in a :class:`Session`.  ``submit`` is the one-shot call; ``stream``
yields token ids as each verify block commits (granularity: one chunk per
committed block, one token per step for greedy); ``serve`` round-robins up
to N concurrent sessions over the same warm runtime, one committed verify
block per session per turn — interleaving is lossless, every session's
stream is bit-identical to serving it alone.  ``stop_tokens`` end a request
early — truncation happens on the committed stream, so it is honoured
identically by every decode × offload combination — and a consumer that
abandons ``stream``/``serve`` mid-flight retires the session with
``finish_reason="aborted"``, leaving the engine warm and reusable.

Each finished request returns a :class:`GenerationResult` carrying a
per-request :class:`Metrics` snapshot (counter deltas for exactly that
request, accrued turn-by-turn so interleaved sessions stay isolated);
``Engine.metrics()`` is the cumulative view.  The keys are the same on
every path — paths that don't exercise a counter report zero.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from enum import Enum
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.chaos import ChaosConfig, ExpertLoadError
from repro.core.cutoff import HardwareProfile
from repro.core import sd as S


class DecodePolicy(str, Enum):
    """How tokens are proposed/committed (axis 1 of the policy model)."""
    GREEDY = "greedy"
    SD = "sd"
    SD_ADAPTIVE = "sd-adaptive"


class OffloadPolicy(str, Enum):
    """Where expert weights live / how they move (axis 2)."""
    NONE = "none"
    SPMOE = "spmoe"
    ADAPMOE = "adapmoe"
    MOE_INFINITY = "moe-infinity"
    ON_DEMAND = "on-demand"


DECODE_POLICIES: Tuple[str, ...] = tuple(p.value for p in DecodePolicy)
OFFLOAD_POLICIES: Tuple[str, ...] = tuple(p.value for p in OffloadPolicy)


def derive_draft_config(cfg: ModelConfig) -> ModelConfig:
    """Default draft for a target: its dense sibling (MoE targets) or a
    half-depth copy (dense targets) — the reduced-scale stand-in for the
    paper's distilled draft models (Table 1)."""
    if cfg.is_moe:
        return dataclasses.replace(
            cfg, num_experts=0, num_experts_per_tok=0, num_shared_experts=0,
            first_dense_layers=0, name=cfg.name + "-draft")
    return dataclasses.replace(cfg, num_layers=max(2, cfg.num_layers // 2),
                               name=cfg.name + "-draft")


@dataclass
class EngineConfig:
    """Everything an :class:`Engine` needs, in one typed object (replaces the
    ``OffloadEngine.__init__`` kwarg pile and the mixed ``--policy`` string).

    ``decode`` × ``offload`` select the serving behaviour; the remaining
    fields parameterize it.  ``draft`` defaults to
    :func:`derive_draft_config` of ``model`` when a draft is needed.
    """
    model: ModelConfig
    draft: Optional[ModelConfig] = None
    decode: str = DecodePolicy.SD.value
    offload: str = OffloadPolicy.NONE.value
    # speculative decoding
    draft_len: int = 4                  # fixed N for decode == "sd"
    min_draft_len: int = 1              # adaptive controller bounds
    max_draft_len: int = 8
    draft_ewma: float = 0.5             # acceptance EWMA smoothing
    # offload plane
    cache_slots: int = 8
    cutoff: Optional[int] = None        # None -> solver/profile/all layers
    k_prefetch: Optional[int] = None    # None -> num_experts_per_tok
    prefetch_mode: str = "worker"
    batched_io: bool = True
    profile: Optional[HardwareProfile] = None
    # session
    max_seq: int = 512
    precompile: bool = True             # trace fast verify path at init
    # resilience plane (see core/chaos.py + the Prefetcher docstring):
    # every knob defaults to today's behaviour — retries on transient I/O,
    # no fault injection, checksums only when chaos is enabled
    chaos: Optional[ChaosConfig] = None
    prefetch_retries: int = 3           # per-task transient-I/O retry budget
    retry_backoff_s: float = 0.002      # exponential backoff base
    task_timeout_s: Optional[float] = None   # per prefetch-task deadline
    drain_timeout_s: float = 30.0       # bound on per-session I/O waits
    verify_payloads: Optional[bool] = None   # None -> on iff chaos enabled
    max_worker_restarts: int = 3        # supervised-worker restart budget
    fail_threshold: int = 3             # consecutive failures -> degraded
    heartbeat_timeout_s: float = 10.0   # wedged-worker detection
    io_retries: int = 3                 # on-demand (decode-critical) retries

    def __post_init__(self):
        self.decode = DecodePolicy(self.decode).value
        self.offload = OffloadPolicy(self.offload).value
        if self.offload != OffloadPolicy.NONE.value and not self.model.is_moe:
            raise ValueError(
                f"offload policy {self.offload!r} requires an MoE target "
                f"(model {self.model.name!r} is dense)")
        if self.decode == DecodePolicy.SD.value and self.draft_len < 1:
            raise ValueError("decode='sd' needs draft_len >= 1")
        if not 1 <= self.min_draft_len <= self.max_draft_len:
            raise ValueError("need 1 <= min_draft_len <= max_draft_len")

    @property
    def needs_draft(self) -> bool:
        return self.decode != DecodePolicy.GREEDY.value

    @property
    def resolved_verify_payloads(self) -> bool:
        """Checksum verification of fetched payloads: explicit setting wins;
        otherwise it is on exactly when fault injection is configured (a
        chaos run without checksums could insert corrupted weights)."""
        if self.verify_payloads is not None:
            return self.verify_payloads
        return self.chaos is not None and self.chaos.enabled

    def resolved_draft(self) -> ModelConfig:
        return self.draft if self.draft is not None \
            else derive_draft_config(self.model)

    @property
    def initial_draft_len(self) -> int:
        """Draft tokens per iteration at session start (0 = no drafting)."""
        if self.decode == DecodePolicy.GREEDY.value:
            return 0
        if self.decode == DecodePolicy.SD_ADAPTIVE.value:
            return self.min_draft_len
        return self.draft_len


@dataclass
class Request:
    """One generation request.  ``prompt`` is a ``[1, P]`` int array (or a
    plain list of token ids).  Generation ends after ``max_new_tokens``
    tokens or — on every decode × offload combination identically — right
    after the first emitted token in ``stop_tokens``.  ``deadline_s`` is a
    per-request wall-clock budget measured from the first decode turn: an
    expired session is retired with ``finish_reason="deadline"`` (already-
    committed tokens are kept) instead of wedging its batchmates' rounds."""
    prompt: Any
    max_new_tokens: int = 32
    stop_tokens: Sequence[int] = ()
    request_id: Optional[str] = None
    deadline_s: Optional[float] = None

    def prompt_array(self) -> jax.Array:
        p = self.prompt
        if not isinstance(p, (jax.Array, np.ndarray)):
            p = jnp.asarray([list(p)], jnp.int32)
        p = jnp.asarray(p, jnp.int32)
        if p.ndim == 1:
            p = p[None, :]
        assert p.ndim == 2 and p.shape[0] == 1, "requests are batch-1 [1, P]"
        return p


# the counters OffloadEngine.counters() exposes — the ONE list the runtime
# snapshot, the per-request delta, and the legacy stats dict all iterate
# (each name is also a Metrics field)
RUNTIME_COUNTER_KEYS = ("lookups", "hits", "on_demand_loads", "prefetched",
                        "evictions", "prefetch_evicted_unused", "h2d_bytes",
                        "compact_bytes",
                        "host_syncs", "verify_syncs", "verify_blocks",
                        "fast_blocks", "fast_fallbacks",
                        "iterations", "drafted", "accepted",
                        # resilience plane (prefetcher/store health)
                        "prefetch_errors", "prefetch_retries",
                        "checksum_failures", "worker_restarts",
                        "degraded_rounds", "io_errors")

# counter fields that accumulate / subtract when combining Metrics
_COUNTERS = ("requests", "tokens") + RUNTIME_COUNTER_KEYS


@dataclass
class Metrics:
    """One typed stats object for every serving path — identical keys
    whether the request ran greedy × none or sd-adaptive × spmoe.  Raw
    counters are stored; ratios are derived properties so per-request
    snapshots and the cumulative view stay consistent under addition."""
    requests: int = 0
    tokens: int = 0
    iterations: int = 0
    drafted: int = 0
    accepted: int = 0
    # offload plane (zero when offload == "none")
    lookups: int = 0
    hits: int = 0
    on_demand_loads: int = 0
    prefetched: int = 0
    evictions: int = 0
    prefetch_evicted_unused: int = 0
    h2d_bytes: int = 0                  # expert bytes handed to device_put
    compact_bytes: int = 0              # expert bytes cache_moe compacts
    host_syncs: int = 0                 # every device->host sync of a turn
    verify_syncs: int = 0               # those made verifying / accepting
    verify_blocks: int = 0
    fast_blocks: int = 0
    fast_fallbacks: int = 0
    # resilience plane (zero on a healthy run)
    prefetch_errors: int = 0
    prefetch_retries: int = 0
    checksum_failures: int = 0
    worker_restarts: int = 0
    degraded_rounds: int = 0
    io_errors: int = 0
    cutoff_layer: int = -1              # configuration echo, not a counter

    # ------------------------------------------------------------- derived
    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.drafted, 1)

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.lookups, 1)

    @property
    def tokens_per_iteration(self) -> float:
        return self.tokens / max(self.iterations, 1)

    # ------------------------------------------------------------ algebra
    def add(self, other: "Metrics") -> "Metrics":
        """Accumulate ``other`` into self (cumulative view)."""
        for f in _COUNTERS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        if other.cutoff_layer >= 0:      # -1 = "no offload plane": adding a
            self.cutoff_layer = other.cutoff_layer  # default-constructed
        return self                      # Metrics must not wipe the echo

    def as_dict(self) -> Dict[str, float]:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d.update(acceptance_rate=self.acceptance_rate,
                 hit_rate=self.hit_rate,
                 tokens_per_iteration=self.tokens_per_iteration)
        return d

    def __getitem__(self, key: str):
        return self.as_dict()[key]


@dataclass
class GenerationResult:
    """Outcome of one request: the committed tokens, why generation stopped
    (``"length"``, ``"stop"``, ``"aborted"`` when the consumer abandoned
    the stream, ``"deadline"`` when the request's wall-clock budget
    expired, ``"cancelled"`` for an explicit :meth:`Session.cancel`, or
    ``"io_error"`` when the offload plane could not load an expert even
    synchronously — the degradation ladder's final rung; committed tokens
    are always a prefix of the fault-free stream, never wrong), and that
    request's Metrics delta."""
    tokens: List[int]
    finish_reason: str
    metrics: Metrics
    request_id: Optional[str] = None

    def token_array(self) -> jax.Array:
        return jnp.asarray(self.tokens, jnp.int32)


class Session:
    """One in-flight request on a (possibly shared) :class:`Engine`.

    Owns the per-request plane: the committed-chunk generator — whose frame
    holds the KV/draft/decode state, lazily started on the first ``turn`` so
    admission order is scheduler-controlled — the emitted-token buffer, the
    finish reason, and a counter-delta LEDGER.  The ledger
    accrues per-turn deltas of the engine-global cumulative counters; a
    single before/after snapshot (how PR 3's serial ``stream`` computed
    per-request metrics) would charge one session with every other session's
    interleaved blocks, so deltas are taken around each generator step
    instead — this is what keeps the per-request Metrics contract intact
    under interleaving.

    Scheduling protocol: call :meth:`turn` repeatedly; each call commits at
    most one verify block (decode-policy-aware — greedy turns commit one
    token, sd/sd-adaptive turns one draft-then-verify block) and returns the
    newly committed tokens, or None once the session is done.  A stop token
    finishes the session mid-chunk; :meth:`abort` retires an abandoned
    session with ``finish_reason="aborted"`` while leaving the engine warm
    and reusable.
    """

    def __init__(self, engine: "Engine", request: Request):
        assert not engine._closed, "engine is closed"
        self.engine = engine
        self.request = request
        self._prompt = request.prompt_array()
        need = self._prompt.shape[1] + request.max_new_tokens + \
            engine._max_block_len() + 1
        assert need <= engine.config.max_seq, (
            f"request needs {need} positions but max_seq is "
            f"{engine.config.max_seq}; raise EngineConfig.max_seq")
        self._stop = set(int(t) for t in request.stop_tokens)
        self.sstats: Dict[str, Any] = {"iterations": 0, "drafted": 0,
                                       "accepted": 0}
        # offload runtimes drive the DecodeState turn API directly (so the
        # serve scheduler can gather several sessions' blocks into one
        # batched verify round); non-offload paths keep the chunk generator
        self.dstate = None              # runtime DecodeState, lazily started
        self.gen = None if engine.runtime is not None else \
            engine._chunk_stream(self._prompt, request.max_new_tokens,
                                 self.sstats)
        self.ledger: Dict[str, int] = {k: 0 for k in RUNTIME_COUNTER_KEYS}
        self.emitted: List[int] = []
        self.result: Optional[GenerationResult] = None
        # per-request deadline: armed on the first decode turn so queueing
        # time behind a long backlog doesn't consume the request's budget
        self._deadline: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.result is not None

    def expired(self) -> bool:
        """True once the request's wall-clock budget (deadline_s) is spent.
        The clock arms on the first decode turn, so time spent queued
        behind a backlog doesn't count against the request."""
        return self._deadline is not None and time.monotonic() > self._deadline

    def _arm_deadline(self):
        if self._deadline is None and self.request.deadline_s is not None:
            self._deadline = time.monotonic() + self.request.deadline_s

    def cancel(self, reason: str = "cancelled"):
        """Retire an unfinished session early (idempotent).  The decode side
        is closed — this session's in-flight prefetch tasks are waited out
        (bounded) and its counters committed — so batchmates and the warm
        engine are unaffected: the session falls out of the scheduling
        round the way a finished one does."""
        if not self.done:
            self._finalize(reason)

    def _step(self, fn):
        """Run one decode-side step under this session's counter ledger
        (per-turn engine-counter deltas)."""
        before = self.engine._counters()
        try:
            return fn()
        finally:
            after = self.engine._counters()
            for k in self.ledger:
                self.ledger[k] += after.get(k, 0) - before.get(k, 0)

    def _advance(self) -> Optional[List[int]]:
        """One solo decode step: start the runtime session on first use
        (prefill), then one committed verify block; None when exhausted."""
        rt = self.engine.runtime
        if rt is not None:
            if self.dstate is None:
                self.dstate = rt.start_session(
                    self._prompt, self.request.max_new_tokens,
                    request_id=self.request.request_id)
            return rt.session_turn(self.dstate)
        try:
            return next(self.gen)
        except StopIteration:
            return None

    def _close_decode(self):
        """Retire the decode side (waits out this session's prefetch tasks
        and commits its device-side counters)."""
        if self.engine.runtime is not None:
            if self.dstate is not None:
                self.engine.runtime.finish_session(self.dstate)
        else:
            self.gen.close()

    def turn(self) -> Optional[List[int]]:
        """Advance one committed verify block.  Returns the newly committed
        tokens (truncated right after a stop token) or None when done.  An
        expired deadline retires the session (``finish_reason="deadline"``)
        and an unrecoverable expert load — the degradation ladder's final
        rung — retires it with ``"io_error"``; neither raises."""
        if self.done:
            return None
        if self.expired():
            self._finalize("deadline")
            return None
        self._arm_deadline()
        try:
            chunk = self._step(self._advance)
        except ExpertLoadError:
            self._finalize("io_error")
            return None
        return self._commit_chunk(chunk)

    def deliver(self, chunk, delta: Dict[str, int]) -> Optional[List[int]]:
        """Commit a chunk produced by a batched cross-session round
        (``OffloadEngine.session_turns``): fold the round's per-session
        counter delta into the ledger, then run the same
        stop-token/finalize logic as a solo :meth:`turn`.  A chunk that is
        an :class:`ExpertLoadError` (this session's block could not load
        its experts even synchronously) retires the session with
        ``finish_reason="io_error"`` — its batchmates are untouched."""
        if self.done:
            return None
        for k in self.ledger:
            self.ledger[k] += delta.get(k, 0)
        if isinstance(chunk, ExpertLoadError):
            self._finalize("io_error")
            return None
        return self._commit_chunk(chunk)

    def _commit_chunk(self, chunk: Optional[List[int]]
                      ) -> Optional[List[int]]:
        if chunk is None:
            self._finalize("length")
            return None
        out: List[int] = []
        for tok in chunk:
            tok = int(tok)
            self.emitted.append(tok)
            out.append(tok)
            if tok in self._stop:
                self._finalize("stop")
                break
        return out

    def abort(self):
        """Retire an unfinished session as ``"aborted"`` (no-op when already
        finished): the decode side is closed — which waits out this
        session's prefetch tasks and commits its counters — so the engine
        stays warm and immediately reusable."""
        if not self.done:
            self._finalize("aborted")

    def _finalize(self, finish: str):
        self._step(self._close_decode)  # offload path retires its DecodeState
        m = Metrics(requests=1, tokens=len(self.emitted),
                    cutoff_layer=self.engine.cutoff_layer)
        if self.engine.runtime is not None:
            for k, v in self.ledger.items():
                setattr(m, k, v)
            if self.dstate is not None:
                # I/O counters come from the session's owner-attributed
                # ledger (finalized by finish_session above): a prefetch
                # load belongs to the session whose task fetched it, not to
                # whichever session's turn it happened to land in
                for k, v in self.dstate.io.items():
                    setattr(m, k, v)
        else:
            m.iterations = self.sstats["iterations"]
            m.drafted = self.sstats["drafted"]
            m.accepted = self.sstats["accepted"]
        self.result = GenerationResult(tokens=list(self.emitted),
                                       finish_reason=finish, metrics=m,
                                       request_id=self.request.request_id)
        self.engine._cum.add(m)
        self.engine.last_result = self.result


class Engine:
    """Long-lived serving engine: one warm expert cache / prefetcher / set of
    compiled steps, many requests.

    ``tparams`` / ``dparams`` may be omitted, in which case the models are
    initialized from ``seed`` / ``draft_seed`` (the convention every example
    and test in this repo uses).  ``close()`` (or use as a context manager)
    stops the prefetch worker.
    """

    def __init__(self, config: EngineConfig, tparams=None, dparams=None, *,
                 seed: int = 0, draft_seed: int = 1):
        from repro.models.registry import build_model   # local: avoid cycle
        self.config = config
        self.target = build_model(config.model)
        self.tparams = tparams if tparams is not None \
            else self.target.init(jax.random.PRNGKey(seed))
        self.draft_cfg = config.resolved_draft() if config.needs_draft else None
        self.draft = build_model(self.draft_cfg) if self.draft_cfg else None
        self.dparams = None
        if self.draft is not None:
            self.dparams = dparams if dparams is not None \
                else self.draft.init(jax.random.PRNGKey(draft_seed))
        self.runtime = None             # OffloadEngine when offload != none
        if config.offload != OffloadPolicy.NONE.value:
            from repro.core.runtime import OffloadEngine
            self.runtime = OffloadEngine(config, self.tparams, self.dparams,
                                         target=self.target, draft=self.draft)
        # per-engine compiled-step caches (warm across requests)
        self._sd_steps: Dict[int, Any] = {}
        self._greedy_step = None
        self._cum = Metrics(cutoff_layer=self.cutoff_layer)
        self.last_result: Optional[GenerationResult] = None
        self.last_batch: List[GenerationResult] = []
        self._closed = False

    # ----------------------------------------------------------- properties
    @property
    def cutoff_layer(self) -> int:
        return self.runtime.cutoff if self.runtime is not None else -1

    # ------------------------------------------------------------- serving
    def submit(self, request: Request) -> GenerationResult:
        """One-shot: run the request to completion, return the result."""
        session = Session(self, request)
        while session.turn() is not None:
            pass
        return session.result

    def stream(self, request: Request) -> Iterator[int]:
        """Yield token ids as each verify block commits.  After exhaustion
        the request's :class:`GenerationResult` is at ``self.last_result``.
        If the consumer abandons the generator mid-stream the request is
        retired with ``finish_reason="aborted"`` and the engine stays warm
        and reusable."""
        session = Session(self, request)
        try:
            while True:
                chunk = session.turn()
                if chunk is None:
                    break
                for tok in chunk:
                    yield tok
                if session.done:       # stop token committed mid-chunk
                    break
        finally:
            session.abort()            # no-op unless abandoned mid-stream

    def serve(self, requests: Sequence[Request], *, concurrency: int = 2
              ) -> Iterator[Tuple[str, int]]:
        """Round-robin scheduler: up to ``concurrency`` sessions at a time
        interleave ONE committed verify block per turn on the single warm
        ExpertCache / Prefetcher / compiled-step set; further requests are
        admitted as sessions finish.  Turns are decode-policy-aware by
        construction — greedy turns commit 1 token, sd / sd-adaptive turns
        one draft-then-verify block of that session's current draft length.

        With an offload runtime, each scheduling round gathers the ready
        sessions' draft blocks into ONE fused cross-session verify dispatch
        (one routing pass, one page-table gather, one ``cache_moe`` launch,
        ≤2 verify syncs per ROUND instead of 2 per session) — concurrency
        makes the hot path cheaper than serial, not merely not-worse.  A
        session that misses falls back alone without dragging its
        batchmates off the fast path.

        Yields ``(request_id, token)`` pairs in commit order (request_id
        falls back to ``"req-<index>"``).  ``self.last_batch`` is reset to
        ``[]`` on this call and holds the per-request
        :class:`GenerationResult` list (submission order) once the iterator
        finishes — including early ``close()`` after the first ``next()``,
        which aborts unfinished sessions; a never-started iterator leaves
        it ``[]``, never a previous batch's results.  Interleaving is
        lossless: each session's token stream is bit-identical to serving
        its request alone (tests/test_sessions.py)."""
        assert concurrency >= 1
        sessions = [Session(self, r) for r in requests]
        names = [s.request.request_id or f"req-{i}"
                 for i, s in enumerate(sessions)]
        self.last_batch = []
        return self._serve_iter(names, sessions, concurrency)

    def _serve_iter(self, names: List[str], sessions: List["Session"],
                    concurrency: int) -> Iterator[Tuple[str, int]]:
        try:
            waiting = list(zip(names, sessions))
            active: List[Tuple[str, Session]] = []
            while active or waiting:
                while waiting and len(active) < concurrency:
                    active.append(waiting.pop(0))
                # deadline sweep: an expired session falls out of the round
                # the way a finished one does — it is retired here (its own
                # prefetch tasks waited out, counters committed) instead of
                # wedging its batchmates' fused verify dispatch
                for _, s in active:
                    if not s.done and s.expired():
                        s.cancel("deadline")
                # batched cross-session round: every started runtime session
                # advances through ONE fused verify dispatch (one routing
                # pass / table gather / cache_moe launch, ≤2 verify syncs for
                # the whole round); fresh admissions run their prefill solo
                # first, and non-offload engines always turn solo.
                round_sts = [s for _, s in active
                             if not s.done and s.dstate is not None]
                delivered: Dict[int, Optional[List[int]]] = {}
                if round_sts:
                    res = self.runtime.session_turns(
                        [s.dstate for s in round_sts])
                    for s, (chunk, delta) in zip(round_sts, res):
                        delivered[id(s)] = s.deliver(chunk, delta)
                for name, s in list(active):
                    chunk = delivered[id(s)] if id(s) in delivered \
                        else s.turn()
                    if s.done:
                        active.remove((name, s))
                    for tok in chunk or ():
                        yield name, tok
        finally:
            for s in sessions:
                s.abort()              # no-op on finished sessions
            self.last_batch = [s.result for s in sessions]

    def serve_all(self, requests: Sequence[Request], *, concurrency: int = 2
                  ) -> List[GenerationResult]:
        """Drain :meth:`serve`; returns the results in request order."""
        for _ in self.serve(requests, concurrency=concurrency):
            pass
        return self.last_batch

    def metrics(self) -> Metrics:
        """Cumulative Metrics across every request this engine served."""
        return dataclasses.replace(self._cum)

    def reset_stats(self):
        """Zero the cumulative counters (engine + cache + prefetcher) so a
        warmed engine reports clean steady-state numbers."""
        self._cum = Metrics(cutoff_layer=self.cutoff_layer)
        if self.runtime is not None:
            self.runtime.reset_stats()

    def close(self):
        if not self._closed and self.runtime is not None:
            self.runtime.close()
        self._closed = True

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------ internals
    def _max_block_len(self) -> int:
        cfg = self.config
        if cfg.decode == DecodePolicy.SD_ADAPTIVE.value:
            return cfg.max_draft_len + 1
        return cfg.initial_draft_len + 1

    def _chunk_stream(self, prompt, max_new_tokens, sstats):
        """The committed-chunk generator for engines WITHOUT an offload
        runtime (offload == none).  Runtime-backed sessions drive the
        DecodeState turn API directly instead (see Session._advance), so the
        serve scheduler can batch several sessions into one verify round."""
        cfg = self.config
        assert self.runtime is None
        if cfg.decode == DecodePolicy.GREEDY.value:
            if self._greedy_step is None:
                self._greedy_step = S.make_greedy_step(self.target)
            return S.greedy_stream(self.target, self.tparams, prompt,
                                   max_new_tokens, cfg.max_seq,
                                   stats=sstats, step=self._greedy_step)
        if cfg.decode == DecodePolicy.SD.value:
            step = self._sd_step_for(cfg.draft_len)
            return S.sd_stream(self.draft, self.target, self.dparams,
                               self.tparams, prompt, max_new_tokens,
                               cfg.draft_len, cfg.max_seq,
                               stats=sstats, step=step)
        return S.sd_adaptive_stream(self.draft, self.target, self.dparams,
                                    self.tparams, prompt, max_new_tokens,
                                    cfg.max_seq, min_len=cfg.min_draft_len,
                                    max_len=cfg.max_draft_len,
                                    ewma=cfg.draft_ewma, stats=sstats,
                                    step_for=self._sd_step_for)

    def _sd_step_for(self, n: int):
        if n not in self._sd_steps:
            self._sd_steps[n] = jax.jit(
                S.make_sd_step(self.draft, self.target, n))
        return self._sd_steps[n]

    def _counters(self) -> Dict[str, int]:
        """Host-only snapshot of the runtime's cumulative counters (empty
        without an offload plane) — cheap enough that Session ledgers take
        it around every turn."""
        return self.runtime.counters() if self.runtime is not None else {}
