"""Device-side expert cache with LRU eviction (paper §3.3 / §4.4).

The cache is a fixed pool of ``slots`` expert-weight buffers resident in
device memory (HBM), plus host-side bookkeeping:

* ``table``       ExpertKey -> slot (the page table)
* ``lru``         access order (OrderedDict; head = eviction candidate)

and — when constructed with ``table_shape=(L, E)`` — a **device-resident
mirror of the page table**, ``table_dev [L, E] -> slot | -1``, maintained
incrementally (one fused int32 scatter per insert covering both the evicted
keys and the fresh ones).  The offload runtime's verification hot path reads
it with a plain device gather, so routing-to-slot translation never touches
the host (see runtime._verify_block).

Slot buffers are updated with donated jitted scatters so the pool is updated
in place — no reallocation, no copy-back to host on eviction (§7: classic
space-time tradeoff, experts always stay host-resident).

Concurrency contract: the prefetch worker and the compute loop both mutate
the cache.  All bookkeeping is under ``self.lock``; because inserts *donate*
``bufs``/``table_dev`` (invalidating the old jax handles), any reader that
dispatches compute against them must snapshot them under the same lock
(``snapshot()``) so a concurrent insert can't delete the handle between read
and dispatch.  In-flight device computation is safe either way — XLA
sequences buffer donation after pending consumers.
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ExpertKey = Tuple[int, int]   # (layer, expert)


def _batched_insert(bufs, stacked, slots):
    """bufs: dict name -> [slots, ...]; stacked: dict name -> [n, ...]."""
    return {name: bufs[name].at[slots].set(stacked[name]) for name in bufs}


def _table_scatter(table, ls, es, vals):
    """table: [L, E] int32; point-scatter of slot ids (or -1 tombstones)."""
    return table.at[ls, es].set(vals)


class ExpertCache:
    """LRU cache of expert weights in device memory.

    Thread-safe: the prefetch worker and the compute loop both mutate it.
    """

    def __init__(self, num_slots: int, buffer_shapes: Dict[str, tuple],
                 dtype=jnp.bfloat16,
                 table_shape: Optional[Tuple[int, int]] = None,
                 chaos=None):
        self.num_slots = num_slots
        self.dtype = dtype
        # optional fault injector (core/chaos.py): inserts may raise an
        # injected transient error BEFORE any bookkeeping mutates
        self.chaos = chaos
        self.bufs = {name: jnp.zeros((num_slots,) + tuple(shape), dtype)
                     for name, shape in buffer_shapes.items()}
        self.table: Dict[ExpertKey, int] = {}
        self.lru: "OrderedDict[ExpertKey, int]" = OrderedDict()
        self.free: List[int] = list(range(num_slots))
        self.lock = threading.RLock()
        # trace counter of the slot scatter, the regression hook of its
        # power-of-two chunking (``insert``)
        self.insert_traces = 0

        @functools.wraps(_batched_insert)
        def _counting_insert(bufs, stacked, slots):
            self.insert_traces += 1            # trace-time side effect only
            return _batched_insert(bufs, stacked, slots)

        self._insert = jax.jit(_counting_insert, donate_argnums=(0,))
        # On the CPU backend jax.device_put of an aligned numpy array
        # aliases it, and the scatter ``insert`` dispatches may run after the
        # caller refills that memory (HostExpertStore.fetch reuses a staging
        # buffer two fetches later), so ``insert`` copies the arrays there
        # first.  Other backends copy them during the device_put call.
        self._copy_staged = all(
            d.platform == "cpu"
            for d in next(iter(self.bufs.values())).devices())
        # device-resident page-table mirror [L, E] -> slot | -1
        self.table_shape = table_shape
        self.table_dev: Optional[jax.Array] = (
            jnp.full(table_shape, -1, jnp.int32)
            if table_shape is not None else None)
        # scatter lengths are padded to powers of two (repeating the final
        # entry — a duplicate set of the same value is deterministic), so the
        # jitted scatter compiles one executable per bucket instead of one
        # per distinct insert size; the trace counter is the regression hook
        self.table_scatter_traces = 0

        def _counting_scatter(table, ls, es, vals):
            self.table_scatter_traces += 1     # trace-time side effect only
            return _table_scatter(table, ls, es, vals)

        self._scatter_table = jax.jit(_counting_scatter, donate_argnums=(0,))
        # stats
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.prefetch_evicted = 0   # evicted before ever being used
        self.h2d_bytes = 0          # bytes handed to jax.device_put

    # ------------------------------------------------------------------ reads
    def contains(self, key: ExpertKey) -> bool:
        with self.lock:
            return key in self.table

    def lookup(self, keys: Sequence[ExpertKey], touch: bool = True
               ) -> Tuple[Dict[ExpertKey, int], List[ExpertKey]]:
        """Split into (hits: key->slot, misses).  Updates LRU + stats."""
        with self.lock:
            hits, misses = {}, []
            for k in keys:
                if k in self.table:
                    hits[k] = self.table[k]
                    self.hits += 1
                    if touch:
                        self.lru.move_to_end(k)
                        self.lru[k] = 1    # mark used
                else:
                    misses.append(k)
                    self.misses += 1
            return hits, misses

    def slots_of(self, keys: Sequence[ExpertKey]) -> jnp.ndarray:
        with self.lock:
            return jnp.array([self.table[k] for k in keys], jnp.int32)

    def snapshot(self) -> Tuple[Dict[str, jax.Array], Optional[jax.Array]]:
        """(bufs, table_dev) captured atomically w.r.t. donating inserts.

        Dispatch device compute against the snapshot while still holding
        ``self.lock`` (dispatch is enqueue-only, so the critical section is
        short); once dispatched, a concurrent donation is sequenced by the
        runtime after the in-flight consumers.
        """
        with self.lock:
            return self.bufs, self.table_dev

    # ----------------------------------------------------------------- writes
    def _allocate(self, n: int, protect: frozenset = frozenset()
                  ) -> Tuple[List[int], List[ExpertKey]]:
        """Reserve n slots, evicting LRU entries as needed.  Lock held.
        Keys in ``protect`` (the insert batch's already-present members) are
        never chosen as victims — evicting them would invalidate the slots
        this very insert is about to return.  Returns (slots, evicted)."""
        slots: List[int] = []
        evicted: List[ExpertKey] = []
        while len(slots) < n:
            if self.free:
                slots.append(self.free.pop())
                continue
            victim = next((k for k in self.lru if k not in protect), None)
            if victim is None:
                raise ValueError(
                    f"batch needs {n} slots but cache capacity is "
                    f"{self.num_slots}; load in waves "
                    f"(see runtime._verify_block)")
            used = self.lru.pop(victim)
            slots.append(self.table.pop(victim))
            evicted.append(victim)
            self.evictions += 1
            if not used:
                self.prefetch_evicted += 1
        return slots, evicted

    def insert(self, keys: Sequence[ExpertKey],
               host_arrays: Dict[str, np.ndarray],
               mark_used: bool = False,
               stats: Optional[Dict[str, int]] = None) -> List[int]:
        """Batched I/O (paper §3.3): one device transfer + one donated scatter
        for the whole group of experts.  host_arrays: name -> [n, ...].

        Asynchronous by construction: the H2D transfer and both scatters are
        dispatched, not awaited — the caller's next consumer of ``bufs`` /
        ``table_dev`` is sequenced after them by the jax runtime, so the
        prefetch worker returns immediately and its H2D overlaps whatever the
        host does next (the next ``HostExpertStore.fetch`` in particular —
        that is the double-buffering contract, see offload.py).  Use
        ``wait()`` for a hard barrier.

        ``stats`` (optional) is credited with this call's ``evictions`` /
        ``prefetch_evicted_unused`` / ``h2d_bytes`` — how per-session I/O
        ledgers attribute eviction and transfer work to the session (or
        prefetch task) that caused it instead of to whoever's turn the
        async load happened to land in.  Keys already resident transfer
        nothing.
        """
        if not keys:
            return []
        if self.chaos is not None:
            # injected transient insert failure, raised before the lock and
            # before ANY bookkeeping — a failed insert leaves the cache
            # exactly as it was, so the caller's retry is safe
            self.chaos.on_insert(len(keys))
        with self.lock:
            if len(set(keys)) > self.num_slots:
                raise ValueError(
                    f"batch of {len(set(keys))} experts exceeds cache "
                    f"capacity {self.num_slots}; load in waves "
                    f"(see runtime._verify_block)")
            # dedupe (first occurrence wins) — a duplicated key must not
            # allocate two slots, that would leak one permanently
            seen = set()
            fresh: List[ExpertKey] = []
            sel: List[int] = []
            for i, k in enumerate(keys):
                if k not in self.table and k not in seen:
                    fresh.append(k)
                    sel.append(i)
                    seen.add(k)
            if fresh:
                ev0, pu0 = self.evictions, self.prefetch_evicted
                slots, evicted = self._allocate(
                    len(fresh), protect=frozenset(keys))
                if stats is not None:        # lock held: counters consistent
                    stats["evictions"] = stats.get("evictions", 0) + \
                        self.evictions - ev0
                    stats["prefetch_evicted_unused"] = \
                        stats.get("prefetch_evicted_unused", 0) + \
                        self.prefetch_evicted - pu0
                if len(sel) == len(host_arrays[next(iter(host_arrays))]):
                    picked = {n: arr for n, arr in host_arrays.items()}
                else:
                    picked = {n: arr[sel] for n, arr in host_arrays.items()}
                host = {n: np.array(arr, self.dtype,
                                    copy=True if self._copy_staged else None)
                        for n, arr in picked.items()}
                nbytes = sum(a.nbytes for a in host.values())
                self.h2d_bytes += nbytes
                if stats is not None:
                    stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + nbytes
                slot_arr = np.asarray(slots, np.int32)
                # one scatter per set bit of the batch size, largest first:
                # the jitted scatter compiles one executable per power of
                # two instead of one per distinct batch size, and moves no
                # padding bytes; slots go in as numpy so no eager convert
                # compiles per length either
                lo = 0
                for bit in reversed(range(len(fresh).bit_length())):
                    n = 1 << bit
                    if not len(fresh) & n:
                        continue
                    stacked = {k: jax.device_put(a[lo:lo + n])
                               for k, a in host.items()}
                    self.bufs = self._insert(self.bufs, stacked,
                                             slot_arr[lo:lo + n])
                    lo += n
                for k, s in zip(fresh, slots):
                    self.table[k] = s
                    self.lru[k] = 1 if mark_used else 0
                    self.lru.move_to_end(k)
                if self.table_dev is not None:
                    ls = np.fromiter((k[0] for k in evicted + fresh), np.int32)
                    es = np.fromiter((k[1] for k in evicted + fresh), np.int32)
                    vals = np.asarray([-1] * len(evicted) + slots, np.int32)
                    # pad to the next power of two by repeating the final
                    # (l, e, val) triple — same index, same value, so the
                    # duplicate set is a deterministic no-op
                    pad = (1 << (len(vals) - 1).bit_length()) - len(vals)
                    if pad:
                        ls = np.concatenate([ls, np.repeat(ls[-1:], pad)])
                        es = np.concatenate([es, np.repeat(es[-1:], pad)])
                        vals = np.concatenate([vals, np.repeat(vals[-1:], pad)])
                    self.table_dev = self._scatter_table(
                        self.table_dev, ls, es, vals)
            # refresh LRU position of already-present keys
            for k in keys:
                if k in self.lru:
                    self.lru.move_to_end(k)
            return [self.table[k] for k in keys]

    # back-compat alias: insert() is already non-blocking; the name documents
    # intent at prefetcher call sites.
    insert_async = insert

    def wait(self):
        """Barrier: ensure all in-flight buffer updates are materialized."""
        with self.lock:
            leaves = jax.tree.leaves(self.bufs)
            if self.table_dev is not None:
                leaves = leaves + [self.table_dev]
        jax.block_until_ready(leaves)

    # ------------------------------------------------------------------ stats
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0

    def reset_stats(self):
        with self.lock:
            self.hits = self.misses = self.evictions = 0
            self.prefetch_evicted = self.h2d_bytes = 0

    def check_invariants(self) -> bool:
        """Property-test hook: page table and LRU agree, no slot aliasing,
        and the device table mirror matches the host page table exactly."""
        with self.lock:
            if set(self.table.keys()) != set(self.lru.keys()):
                return False
            slots = list(self.table.values())
            if len(slots) != len(set(slots)):
                return False
            if any(s < 0 or s >= self.num_slots for s in slots):
                return False
            if set(slots) & set(self.free):
                return False
            if len(slots) + len(self.free) != self.num_slots:
                return False
            if self.table_dev is not None:
                tdev = np.asarray(self.table_dev)
                want = np.full(self.table_shape, -1, np.int32)
                for (l, e), s in self.table.items():
                    want[l, e] = s
                if not np.array_equal(tdev, want):
                    return False
            return True
