"""Bring-up check on one TPU chip: serve Mixtral-8x7B at its published widths.

Drives the serving path a user calls — ``EngineConfig`` -> ``Engine`` ->
``OffloadEngine`` with ``decode="sd"`` and ``offload="spmoe"`` — on random
weights made from ``--seed``, at Mixtral's published widths (d_model 4096,
d_ff 14336, 32/8 heads of 128, 8 experts top-2, vocab 32000, bfloat16) with
the target and its Mistral-7B draft both cut to ``LAYERS`` layers.  Four
seeded requests are served once at concurrency 1 (solo verify blocks) and
once at concurrency 2 (fused cross-session rounds) on one warm engine.

It fails (non-zero exit, no result line) unless:

* JAX runs on a TPU — there is no CPU fallback;
* every request finishes with ``length``;
* each run verified blocks on the fast path and prefetched experts;
* the resilience counters (prefetch errors, worker restarts, degraded
  rounds, I/O errors) are all zero;
* the lowered fast verify steps contain ``tpu_custom_call`` (the Pallas
  ``cache_moe`` kernel, not the jnp reference);
* every served token is target-only greedy decoding's top-1, computed on
  the served prefix, or differs from it at a bfloat16 near-tie that
  rounding in the served path may flip: the served token's logit trails
  the top-1 by no more than ``GAP_TOL_EPS`` bfloat16 epsilons of it (with
  random weights and a 32000-token vocabulary the top two bfloat16 logits
  are often exactly equal), or the router's k-th and (k+1)-th gate logits
  for the token before are that close (the token's experts then depend on
  rounding, and its logits change wholesale).

The last line of standard output is one JSON object with the device.

    python chip_smoke.py
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "mixtral-8x7b"
LAYERS = 1            # depth cut: one whole layer of the target and draft
CACHE_SLOTS = 7       # of the 8 experts per layer: misses keep prefetch busy
DRAFT_LEN = 4         # solo block T = 5; two fused blocks T = 10
PROMPT_LEN = 16
NEW_TOKENS = 16
REQUESTS = 4
# a served token may differ from the reference only at a near-tie: within
# this many bfloat16 epsilons (2**-7) of the reference's top-1 logit
GAP_TOL_EPS = 4
BF16_EPS = 2.0 ** -7
HEALTH_KEYS = ("prefetch_errors", "worker_restarts", "degraded_rounds",
               "io_errors")


class CompileLog:
    """Sums JAX's compile-time events and persistent-cache hits/misses."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def lowered_has_kernel(fn, *args) -> bool:
    """Lower ``fn`` at the shapes of ``args`` (no buffers are read, so a
    pool donated since is no concern) and look for the Mosaic kernel."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if isinstance(a, jax.Array) else a, args)
    return "tpu_custom_call" in fn.lower(*shapes).as_text()


def run(eng, reqs, concurrency, label, failures):
    t0 = time.perf_counter()
    results = eng.serve_all(reqs, concurrency=concurrency)
    wall = time.perf_counter() - t0
    print(f"[{label}] concurrency={concurrency} serve_s={wall:.3f}")
    for r in results:
        m = r.metrics
        print(f"[{label} {r.request_id}] finish={r.finish_reason} "
              f"tokens={m.tokens} verify_blocks={m.verify_blocks} "
              f"fast_blocks={m.fast_blocks} "
              f"fast_fallbacks={m.fast_fallbacks} "
              f"prefetched={m.prefetched} "
              f"on_demand_loads={m.on_demand_loads} "
              f"hit_rate={m.hit_rate:.3f} "
              f"acceptance_rate={m.acceptance_rate:.3f} "
              f"host_syncs={m.host_syncs} verify_syncs={m.verify_syncs} "
              f"h2d_bytes={m.h2d_bytes}")
        if r.finish_reason != "length":
            failures.append(f"{label} {r.request_id}: finish_reason "
                            f"{r.finish_reason!r}")
    fast = sum(r.metrics.fast_blocks for r in results)
    prefetched = sum(r.metrics.prefetched for r in results)
    print(f"[{label}] fast_blocks={fast} prefetched={prefetched}")
    if fast == 0:
        failures.append(f"{label}: no block verified on the fast path")
    if prefetched == 0:
        failures.append(f"{label}: no expert was prefetched")
    return results


def tolerance(top: float) -> float:
    return GAP_TOL_EPS * BF16_EPS * max(1.0, abs(top))


def flipped_routing(target, tparams, cache, tokens, pos):
    """For a depth-1 target, the last of ``tokens``: the gap between its
    k-th and (k+1)-th gate logits with that gap's tolerance, and its output
    logits with those two experts swapped — the routing a near-tie may
    give it on another rounding path."""
    from repro.models import layers as L
    cfg = target.cfg
    assert cfg.num_layers == 1, "the routing flip is computed at depth 1"
    lp, moe = tparams["layers"], tparams["layers"]["moe"]
    x = jnp.take(tparams["wte"], tokens, axis=0)
    a, _ = L.attention_decode(
        jax.tree.map(lambda w: w[0], lp["attn"]),
        L.rms_norm(x, lp["ln1"][0], cfg.norm_eps),
        jax.tree.map(lambda c: c[0], cache["layers"]), pos, cfg)
    x = (x + a)[:, -1:]                                           # [1, 1, d]
    h = L.rms_norm(x, lp["ln2"][0], cfg.norm_eps)
    gl = np.asarray(h[0, 0].astype(jnp.float32) @ moe["gate"][0])
    order = np.argsort(-gl, kind="stable")
    k = cfg.num_experts_per_tok
    margin = float(gl[order[k - 1]] - gl[order[k]])
    ids = list(order[:k - 1]) + [order[k]]
    w = np.exp(gl[ids] - gl[ids].max())
    y = sum(float(wi) * L.ffn_forward(
        {n: moe[n][0, e] for n in ("wg", "wu", "wd")}, h, "swiglu")
        for wi, e in zip(w / w.sum(), ids))
    x = x + y.astype(x.dtype)
    lg = target._head(tparams, L.rms_norm(x, tparams["ln_f"], cfg.norm_eps))
    return margin, tolerance(float(gl[order[k - 1]])), \
        np.asarray(lg[0, 0], np.float32)


def check_tokens(target, tparams, step, prompt, served, max_seq, label,
                 failures):
    """Token-for-token check against target-only greedy decoding, teacher
    forced: ``greedy_generate``'s own prefill and decode step run along the
    served tokens, so up to the first divergence this is exactly that
    reference, and every later position is checked too.  A served token
    other than the reference's top-1 must come from a bfloat16 near-tie:
    of the output logits, or of the router's k-th and (k+1)-th expert,
    where rounding decides which experts the token gets: the served token
    must then be the top-1 of the logits with those experts swapped."""
    if len(served) != NEW_TOKENS:
        failures.append(f"{label}: served {len(served)} tokens")
        return
    logits, cache = target.prefill(tparams, prompt, max_seq)
    pos = prompt.shape[1]
    ctx = (target.init_cache(1, max_seq), prompt, 0)   # input of `logits`
    diverged = 0
    for i, tok in enumerate(served):
        if i:
            prev = jnp.asarray([[served[i - 1]]], jnp.int32)
            ctx = (cache, prev, pos)
            lg, cache, _ = step(tparams, cache, prev, jnp.int32(pos))
            logits, pos = lg[:, -1], pos + 1
        lg = np.asarray(logits[0], np.float32)
        top2 = np.argsort(-lg, kind="stable")[:2]
        if tok == top2[0]:
            continue
        diverged += 1
        gap12 = float(lg[top2[0]] - lg[top2[1]])
        gap = float(lg[top2[0]] - lg[tok])
        tol = tolerance(float(lg[top2[0]]))
        msg = (f"[{label}] diverges at position {i}: served {tok}, "
               f"reference {top2[0]}; reference top-1/top-2 gap {gap12:.6g}, "
               f"top-1 minus served {gap:.6g}, tolerance {tol:.6g}")
        if gap > tol:
            margin, rtol, flg = flipped_routing(target, tparams, *ctx)
            fgap = float(flg.max() - flg[tok])
            msg += (f"; router k-th minus (k+1)-th gate logit {margin:.6g}, "
                    f"tolerance {rtol:.6g}; with those experts swapped, "
                    f"top-1 minus served {fgap:.6g}")
            if margin > rtol or fgap > tolerance(float(flg.max())):
                failures.append(f"{label}: position {i} is no bfloat16 "
                                f"near-tie of logits or routing")
        print(msg)
    print(f"[{label}] {len(served) - diverged}/{len(served)} tokens are the "
          f"greedy reference's top-1")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU present (JAX backend is {backend!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.core import sd as S
        from repro.core.engine import Engine, EngineConfig, Request
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import model_pair
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    cache_dir = enable_compile_cache()
    n_entries = len(list(Path(cache_dir).glob("*"))) \
        if Path(cache_dir).is_dir() else 0
    print(f"compile cache: {cache_dir} ({n_entries} entries at start)")
    clog = CompileLog()

    cfg, dcfg, cuts = model_pair(ARCH, LAYERS)
    for cut in cuts:
        print(f"cut: {cut}")
    print(f"target: d_model={cfg.d_model} d_ff={cfg.moe_d_ff} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
          f"experts={cfg.num_experts} top{cfg.num_experts_per_tok} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}; "
          f"cache_slots={CACHE_SLOTS} draft_len={DRAFT_LEN}")
    max_seq = PROMPT_LEN + NEW_TOKENS + DRAFT_LEN + 16
    config = EngineConfig(model=cfg, draft=dcfg, decode="sd",
                          offload="spmoe", cache_slots=CACHE_SLOTS,
                          draft_len=DRAFT_LEN, max_seq=max_seq)
    prompts = [jax.random.randint(jax.random.PRNGKey(args.seed + 2 + i),
                                  (1, PROMPT_LEN), 0, cfg.vocab_size)
               for i in range(REQUESTS)]

    def requests():
        return [Request(prompt=p, max_new_tokens=NEW_TOKENS,
                        request_id=f"req-{i}") for i, p in enumerate(prompts)]

    failures = []
    t0 = time.perf_counter()
    eng = Engine(config, seed=args.seed, draft_seed=args.seed + 1)
    try:
        print(f"engine init: {time.perf_counter() - t0:.3f} s "
              f"(compile so far {clog.seconds:.3f} s)")
        solo = run(eng, requests(), 1, "solo", failures)
        fused = run(eng, requests(), 2, "fused", failures)
        rt = eng.runtime
        counters = rt.counters()
        print("health: " + " ".join(f"{k}={counters[k]}"
                                    for k in HEALTH_KEYS)
              + f" ({rt.health()})")
        failures += [f"{k} = {counters[k]}" for k in HEALTH_KEYS
                     if counters[k]]
        bufs, table = rt.cache.snapshot()
        hist = jnp.zeros(rt._hist_shape, jnp.float32)
        tcache = eng.target.init_cache(1, max_seq)
        block = jnp.zeros((1, DRAFT_LEN + 1), jnp.int32)
        kern_solo = lowered_has_kernel(rt._verify_fast, rt._rp, bufs, table,
                                       hist, block, 0, tcache)
        kern_fused = lowered_has_kernel(
            rt._verify_fast_batched, rt._rp, bufs, table, (hist, hist),
            (block, block), (0, 0), (tcache, tcache))
        print(f"tpu_custom_call in fast verify step: solo={kern_solo} "
              f"fused={kern_fused}")
        if not (kern_solo and kern_fused):
            failures.append("fast verify step lowered without the Mosaic "
                            "cache_moe kernel")
    finally:
        eng.close()
    # the serving peak, before the reference check allocates its own
    stats = dev.memory_stats() or {}

    step = S.make_greedy_step(eng.target)
    same = all(a.tokens == b.tokens for a, b in zip(solo, fused))
    print(f"solo and fused runs served identical tokens: {same}")
    for i, prompt in enumerate(prompts):
        for res, label in ((solo[i], "solo"), (fused[i], "fused")):
            check_tokens(eng.target, eng.tparams, step, prompt, res.tokens,
                         max_seq, f"{label} {res.request_id}", failures)

    peak = stats.get("peak_bytes_in_use")
    limit = stats.get("bytes_limit")
    print(f"HBM peak while serving: {peak / 2**30:.3f} GiB of "
          f"{limit / 2**30:.3f} GiB" if peak and limit
          else f"HBM peak: not reported ({stats})")
    print(f"compile: {clog.seconds:.3f} s; persistent cache "
          f"hits={clog.cache_hits} misses={clog.cache_misses}")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
