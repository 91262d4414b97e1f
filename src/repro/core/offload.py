"""Host-resident expert store (the offloaded side of the cache).

All expert weights stay in host memory for the lifetime of the engine —
eviction never copies back (paper §7).  ``fetch`` copies each requested
expert's rows straight into **preallocated, contiguous staging buffers** (the
pinned-memory analogue on this backend): one ``np.copyto`` per key and
weight tensor, no per-call allocation, no temporary of the batch's size
(``np.take(..., out=...)`` with its default ``mode="raise"`` would gather
into a fresh batch-sized temporary, then copy that into ``out``).  Both
parts of every key are range-checked first, so a bad layer or expert id
raises instead of reading a neighbouring expert.  Per *thread*, two staging
buffers alternate (double buffering) so the H2D transfer dispatched by
``ExpertCache.insert`` on batch *i* overlaps the host gather of batch *i+1*
— the prefetch worker's pipeline never stalls on its own staging memory.

The staging ring is **thread-local**: the prefetch worker and the compute
loop both call ``fetch`` concurrently (worker prefetch vs. the slow path's
miss waves), and a shared ring would let one thread's gather overwrite the
other's staged weights before the device copy happens.

Payload integrity: the canonical host arrays are the ground truth, and
every (layer, expert) has a lazily-memoized CRC32 over its weight tensors.
``fetch_verified`` re-checksums the *staged* copy against the canonical
sum and raises :class:`~repro.core.chaos.PayloadCorruption` on mismatch —
a corrupted transfer (chaos-injected or real) is quarantined in staging
and never reaches the device cache; the caller's retry loop refetches.
An optional :class:`~repro.core.chaos.ChaosInjector` makes ``fetch``
fallible on purpose (transient errors, latency spikes, staged-byte
corruption) for resilience tests and the ``--mode chaos`` benchmark.
"""
from __future__ import annotations

import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cache import ExpertKey
from repro.core.chaos import ChaosInjector, PayloadCorruption

_NUM_STAGING = 2          # double buffer: gather batch i+1 while i transfers


class HostExpertStore:
    """Extracts per-(layer, expert) weights from a target model's params and
    keeps them as host numpy arrays."""

    def __init__(self, cfg: ModelConfig, params, staging_batch: int = 16,
                 chaos: Optional[ChaosInjector] = None):
        assert cfg.is_moe, "HostExpertStore requires an MoE config"
        self.cfg = cfg
        moe = params["layers"]["moe"]        # stacked [L_moe, E, ...]
        self.names = [n for n in ("wg", "wu", "wd") if n in moe]
        self._store = {n: np.ascontiguousarray(moe[n]) for n in self.names}
        self.num_layers = self._store[self.names[0]].shape[0]
        self.num_experts = self._store[self.names[0]].shape[1]
        # flat [L*E, ...] views for single-gather batched reads
        self._flat = {n: self._store[n].reshape(
            (self.num_layers * self.num_experts,) + self._store[n].shape[2:])
            for n in self.names}
        # preallocated staging rings, one per calling thread (grown on
        # demand, never shrunk)
        self._stage_batch = max(1, staging_batch)
        self._tls = threading.local()
        self.chaos = chaos
        self.checksum_failures = 0         # staged payloads that failed CRC
        self._sums: Dict[ExpertKey, int] = {}   # canonical CRC32 per key
        self._sums_lock = threading.Lock()

    def _alloc_stage(self, cap: int) -> Dict[str, np.ndarray]:
        return {n: np.empty((cap,) + self._store[n].shape[2:],
                            self._store[n].dtype) for n in self.names}

    def _thread_ring(self, min_cap: int):
        tls = self._tls
        if getattr(tls, "stages", None) is None or tls.cap < min_cap:
            tls.cap = max(self._stage_batch, min_cap)
            tls.stages = [self._alloc_stage(tls.cap)
                          for _ in range(_NUM_STAGING)]
            tls.i = 0
        return tls

    def buffer_shapes(self) -> Dict[str, tuple]:
        return {n: self._store[n].shape[2:] for n in self.names}

    def expert_bytes(self) -> int:
        return int(sum(self._store[n][0, 0].nbytes for n in self.names))

    def _row(self, key: ExpertKey) -> int:
        """Flat row of ``key`` in the ``[L*E, ...]`` views; raises
        ``IndexError`` unless both its layer and its expert are in range."""
        l, e = key
        if not (0 <= l < self.num_layers and 0 <= e < self.num_experts):
            raise IndexError(
                f"expert key {(l, e)} outside {self.num_layers} layers x "
                f"{self.num_experts} experts")
        return l * self.num_experts + e

    def fetch(self, keys: Sequence[ExpertKey]) -> Dict[str, np.ndarray]:
        """Batched host read: name -> [len(keys), ...] staged contiguously.

        The returned arrays are views into the calling thread's current
        staging buffer; they stay valid until that thread's next-but-one
        ``fetch`` (double buffering) — long enough for
        ``ExpertCache.insert`` to dispatch the H2D transfer, which copies
        them first where ``device_put`` would alias them (the CPU).
        """
        idx = [self._row(k) for k in keys]    # raises before any copy
        if self.chaos is not None:
            self.chaos.on_fetch(len(keys))     # may spike (sleep) or raise
        n_keys = len(keys)
        tls = self._thread_ring(n_keys)
        stage = tls.stages[tls.i]
        tls.i = (tls.i + 1) % _NUM_STAGING
        out = {}
        for n in self.names:
            src, dst = self._flat[n], stage[n]
            for j, i in enumerate(idx):        # row copies: no temporary
                np.copyto(dst[j], src[i])
            out[n] = dst[:n_keys]
        if self.chaos is not None:
            self.chaos.maybe_corrupt(out)      # poisons the STAGED copy only
        return out

    # ------------------------------------------------------------- integrity
    def expected_checksum(self, key: ExpertKey) -> int:
        """Canonical CRC32 of one expert's weight tensors (memoized — the
        host store is immutable for the engine's lifetime)."""
        with self._sums_lock:
            s = self._sums.get(key)
        if s is None:
            i = self._row(key)
            s = 0
            for n in self.names:
                s = zlib.crc32(self._flat[n][i].tobytes(), s)
            with self._sums_lock:
                self._sums[key] = s
        return s

    def payload_checksum(self, arrays: Dict[str, np.ndarray], i: int) -> int:
        """CRC32 of row ``i`` of a fetched batch, in canonical name order."""
        s = 0
        for n in self.names:
            s = zlib.crc32(np.ascontiguousarray(arrays[n][i]).tobytes(), s)
        return s

    def verify_payload(self, keys: Sequence[ExpertKey],
                       arrays: Dict[str, np.ndarray]) -> List[int]:
        """Indices of fetched rows whose staged bytes do not match the
        canonical checksum (empty = clean batch)."""
        return [i for i, k in enumerate(keys)
                if self.payload_checksum(arrays, i) != self.expected_checksum(k)]

    def fetch_verified(self, keys: Sequence[ExpertKey]
                       ) -> Dict[str, np.ndarray]:
        """``fetch`` + checksum verification: a corrupted staged payload is
        quarantined (never returned for insertion) by raising
        :class:`PayloadCorruption` — the caller's retry loop refetches."""
        arrays = self.fetch(keys)
        bad = self.verify_payload(keys, arrays)
        if bad:
            self.checksum_failures += len(bad)
            raise PayloadCorruption(
                f"checksum mismatch on fetched experts "
                f"{[tuple(keys[i]) for i in bad]}")
        return arrays

    def strip_experts(self, params):
        """Return params with expert tensors removed (host-only now) — the
        resident footprint the offload engine actually keeps on device.

        Copies every dict on the path to ``params["layers"]["moe"]``
        explicitly so the caller's nested params are never mutated (a
        ``jax.tree.map`` identity copy is an implementation detail of the
        pytree registry, not a documented isolation guarantee).
        """
        import jax.numpy as jnp
        out = dict(params)
        out["layers"] = dict(params["layers"])
        out["layers"]["moe"] = dict(params["layers"]["moe"])
        for n in self.names:
            out["layers"]["moe"][n] = jnp.zeros((0,), jnp.bfloat16)
        return out
