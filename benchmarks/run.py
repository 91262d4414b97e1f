"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall-clock of the
measured operation; derived = the figure's headline metric).  The TPOT
numbers come from the calibrated event-driven simulator (core/simulator.py,
see DESIGN.md §2 — this container has no GPU/PCIe); hit rates are
additionally cross-checked against the REAL OffloadEngine on a reduced
config in ``engine_real``.

    PYTHONPATH=src python -m benchmarks.run              # all
    PYTHONPATH=src python -m benchmarks.run fig9 table3  # subset
    PYTHONPATH=src python -m benchmarks.run --mode offload [--out F.json]
                                          # real-engine offload micro-bench ->
                                          # BENCH_offload.json (perf tracking)
    PYTHONPATH=src python -m benchmarks.run --mode sessions [--out F.json]
                                          # multi-session serial vs concurrent
                                          # throughput -> BENCH_sessions.json
    PYTHONPATH=src python -m benchmarks.run --mode chaos [--out F.json]
                                          # fault-injected serving: throughput
                                          # degradation curve vs fault rate,
                                          # lossless gate -> BENCH_chaos.json
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.core.simulator import (DATASETS, ENVS, SIM_MODELS, SimConfig,
                                  Simulator, simulate)

SEEDS = (0, 1, 2)
POLICIES = ("on-demand", "moe-infinity", "adapmoe", "spmoe")
POLICY_LABEL = {"on-demand": "MO", "moe-infinity": "MI", "adapmoe": "AdapMoE",
                "spmoe": "SP-MoE"}


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def fig9_datasets():
    """Figure 9: TPOT across four datasets (mixtral, three envs)."""
    for env in ("3090", "4090", "a100"):
        for ds in DATASETS:
            base = None
            for pol in POLICIES:
                t0 = time.perf_counter()
                rs = [Simulator(SIM_MODELS["mixtral-8x7b"], ENVS[env],
                                SimConfig(policy=pol, dataset=ds, seed=s,
                                          out_tokens=100)).run()
                      for s in SEEDS]
                wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
                tpot = float(np.mean([r.tpot for r in rs]))
                if base is None:
                    base = tpot
                _row(f"fig9.{env}.{ds}.{POLICY_LABEL[pol]}", wall,
                     f"tpot_ms={tpot*1e3:.1f};speedup_vs_MO={base/tpot:.2f}")


def fig10_models():
    """Figure 10: TPOT across the three model pairs and three envs."""
    for model in SIM_MODELS:
        for env in ("3090", "4090", "a100"):
            base = None
            for pol in POLICIES:
                t0 = time.perf_counter()
                rs = [Simulator(SIM_MODELS[model], ENVS[env],
                                SimConfig(policy=pol, seed=s, out_tokens=100)).run()
                      for s in SEEDS]
                wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
                tpot = float(np.mean([r.tpot for r in rs]))
                if base is None:
                    base = tpot
                _row(f"fig10.{model}.{env}.{POLICY_LABEL[pol]}", wall,
                     f"tpot_ms={tpot*1e3:.1f};speedup_vs_MO={base/tpot:.2f}")


def table3_hit_rate():
    """Table 3: hit rates across datasets/models/frameworks."""
    for model in SIM_MODELS:
        for ds in DATASETS:
            for pol in POLICIES:
                t0 = time.perf_counter()
                rs = [Simulator(SIM_MODELS[model], ENVS["4090"],
                                SimConfig(policy=pol, dataset=ds, seed=s,
                                          out_tokens=100)).run()
                      for s in SEEDS]
                wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
                hit = float(np.mean([r.hit_rate for r in rs]))
                _row(f"table3.{model}.{ds}.{POLICY_LABEL[pol]}", wall,
                     f"hit_rate={hit:.3f}")


def fig11_memory():
    """Figure 11: TPOT vs GPU memory (deepseek pair, HumanEval, env3)."""
    for mem in (7, 12, 16, 24, 32, 39):
        for pol in POLICIES:
            t0 = time.perf_counter()
            rs = [Simulator(SIM_MODELS["deepseek-v2-lite-16b"], ENVS["a100"],
                            SimConfig(policy=pol, gpu_mem_gb=float(mem),
                                      seed=s, out_tokens=100)).run()
                  for s in SEEDS]
            wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
            tpot = float(np.mean([r.tpot for r in rs]))
            _row(f"fig11.mem{mem}GB.{POLICY_LABEL[pol]}", wall,
                 f"tpot_ms={tpot*1e3:.1f}")


def fig12_ablation():
    """Figure 12: baseline -> +vanilla prefetch -> +worker -> +batched IO."""
    for model in SIM_MODELS:
        t0 = time.perf_counter()
        variants = {
            "baseline": dict(policy="on-demand"),
            "vp": dict(policy="spmoe", worker_prefetch=False, batched_io=False),
            "wp": dict(policy="spmoe", worker_prefetch=True, batched_io=False),
            "wp+b": dict(policy="spmoe", worker_prefetch=True, batched_io=True),
        }
        base = None
        for name, kw in variants.items():
            tpot = float(np.mean([simulate(model, seed=s, out_tokens=100,
                                           **kw).tpot for s in SEEDS]))
            if base is None:
                base = tpot
            wall = (time.perf_counter() - t0) * 1e6
            _row(f"fig12.{model}.{name}", wall,
                 f"tpot_ms={tpot*1e3:.1f};speedup={base/tpot:.2f}")


def fig13_draft_len():
    """Figure 13: TPOT vs draft token length across envs (mixtral)."""
    for env in ("3090", "4090", "a100"):
        for n in (1, 2, 4, 6, 8):
            for pol in POLICIES:
                t0 = time.perf_counter()
                rs = [Simulator(SIM_MODELS["mixtral-8x7b"], ENVS[env],
                                SimConfig(policy=pol, draft_len=n, seed=s,
                                          out_tokens=100)).run()
                      for s in SEEDS]
                wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
                tpot = float(np.mean([r.tpot for r in rs]))
                _row(f"fig13.{env}.N{n}.{POLICY_LABEL[pol]}", wall,
                     f"tpot_ms={tpot*1e3:.1f}")


def fig14_cutoff():
    """Figure 14: TPOT vs cutoff layer (U-shape mixtral/phi, monotone ds)."""
    for model in SIM_MODELS:
        L = SIM_MODELS[model].num_layers
        for c in (0, 5, 10, 15, 20, 25, L - 1):
            c = min(c, L - 1)
            t0 = time.perf_counter()
            tpot = float(np.mean([simulate(model, policy="spmoe", cutoff=c,
                                           seed=s, out_tokens=100).tpot
                                  for s in SEEDS]))
            wall = (time.perf_counter() - t0) * 1e6 / len(SEEDS)
            _row(f"fig14.{model}.cutoff{c}", wall, f"tpot_ms={tpot*1e3:.1f}")


def fig2_observations():
    """Figure 2: activation overlap of neighbouring tokens + prediction-
    strategy entropies."""
    from repro.core.predictor import strategy_entropies
    for model in SIM_MODELS:
        sim = Simulator(SIM_MODELS[model], ENVS["4090"], SimConfig(seed=0))
        t0 = time.perf_counter()
        overlaps = []
        for _ in range(200):
            blk = sim._sample_tokens(0, 2)
            a, b = set(blk[0].tolist()), set(blk[1].tolist())
            overlaps.append(len(a & b) / len(a | b))
        wall = (time.perf_counter() - t0) * 1e6
        _row(f"fig2b.{model}.overlap", wall,
             f"mean_jaccard={float(np.mean(overlaps)):.3f}")
        E = SIM_MODELS[model].num_experts
        probs = np.exp(np.random.default_rng(0).normal(size=(256, E)) * 2.5)
        probs /= probs.sum(-1, keepdims=True)
        ent = strategy_entropies(probs, sim.history[0] + 1)
        _row(f"fig2c.{model}.entropy", wall,
             f"random={ent['random']:.2f};coarse={ent['coarse_grained']:.2f};"
             f"gating={ent['gating_based']:.2f}")


def fig4_latency_split():
    """Figure 4: decode-iteration latency distribution (loading dominates)."""
    for model in SIM_MODELS:
        t0 = time.perf_counter()
        r = simulate(model, policy="on-demand", seed=0, out_tokens=100)
        wall = (time.perf_counter() - t0) * 1e6
        tot = r.io_time + r.compute_time + r.draft_time
        _row(f"fig4.{model}", wall,
             f"loading={r.io_time/tot:.2f};draft={r.draft_time/tot:.2f};"
             f"compute={r.compute_time/tot:.2f}")


def engine_real():
    """Cross-check: REAL serving engine (reduced mixtral, CPU) — SP-MoE's
    hit rate must beat on-demand's, as in the simulator.  Goes through the
    unified request API (core/engine.py)."""
    import jax
    from repro.configs.registry import get_config
    from repro.core.engine import Engine, EngineConfig, Request

    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    tparams = None
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size)
    hits = {}
    for pol in ("on-demand", "spmoe"):
        config = EngineConfig(model=cfg, decode="sd", offload=pol,
                              cache_slots=8, draft_len=4, max_seq=64)
        with Engine(config, tparams) as eng:
            tparams = eng.tparams          # share the init across engines
            t0 = time.perf_counter()
            res = eng.submit(Request(prompt=prompt, max_new_tokens=16))
            wall = (time.perf_counter() - t0) * 1e6
        m = res.metrics
        hits[pol] = m.hit_rate
        _row(f"engine_real.mixtral-reduced.{POLICY_LABEL[pol]}", wall,
             f"hit_rate={m.hit_rate:.3f};prefetched={m.prefetched}")
    assert hits["spmoe"] >= hits["on-demand"]


def offload_micro(out_path: str = "BENCH_offload.json"):
    """Real serving-engine micro-benchmark: TPOT / hit rate / on-demand
    loads / host-sync count, spmoe vs on-demand, written to ``out_path`` so
    the perf trajectory of the verification hot path is tracked PR over PR.

    Goes through the unified request API: one Engine per (setting, policy)
    serves a warmup request (compiles fast+slow verify paths — the fast
    path is additionally pre-traced at engine init) followed by 3 measured
    requests; Metrics snapshots are per-request deltas, so no stat reset is
    needed between runs and the best-of-3 reflects steady-state decode.
    """
    import jax
    from repro.configs.registry import get_config
    from repro.core.engine import Engine, EngineConfig, Request

    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    tparams = dparams = None
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                cfg.vocab_size)
    n_tokens = 24
    total_experts = cfg.num_moe_layers * cfg.num_experts
    settings = {
        "tight": 2 * cfg.num_experts,    # I/O-bound: constant miss pressure
        "ample": total_experts,          # hot-path-bound: fast path engages
    }
    results = {}
    for setting, slots in settings.items():
        for pol in ("spmoe", "on-demand"):
            config = EngineConfig(model=cfg, decode="sd", offload=pol,
                                  cache_slots=slots, draft_len=4, max_seq=96)
            with Engine(config, tparams, dparams) as eng:
                tparams, dparams = eng.tparams, eng.dparams  # share init
                eng.submit(Request(prompt=prompt, max_new_tokens=n_tokens))
                best = None
                for _ in range(3):           # best-of-3: CPU wall clocks are
                    t0 = time.perf_counter()  # noisy; min is noise-robust
                    res = eng.submit(Request(prompt=prompt,
                                             max_new_tokens=n_tokens))
                    wall = (time.perf_counter() - t0) * 1e6
                    if best is None or wall < best[1]:
                        best = (res.metrics, wall)
            m, wall = best
            tpot_s = wall / 1e6 / max(m.tokens, 1)
            results[f"{setting}.{pol}"] = {
                "cache_slots": slots,
                "tpot_s": tpot_s,
                "hit_rate": m.hit_rate,
                "on_demand_loads": m.on_demand_loads,
                "host_syncs": m.host_syncs,
                "verify_syncs": m.verify_syncs,
                "verify_blocks": m.verify_blocks,
                "fast_blocks": m.fast_blocks,
                "fast_fallbacks": m.fast_fallbacks,
                "prefetched": m.prefetched,
                "acceptance_rate": m.acceptance_rate,
            }
            _row(f"offload.{setting}.{POLICY_LABEL[pol]}", wall,
                 f"tpot_ms={tpot_s*1e3:.2f};"
                 f"hit_rate={m.hit_rate:.3f};"
                 f"host_syncs={m.host_syncs};"
                 f"fast_blocks={m.fast_blocks}")
    results["meta"] = {
        "model": "mixtral-8x7b.reduced", "draft_len": 4,
        "n_tokens": n_tokens,
        "speedup_spmoe_vs_on_demand_tight":
            results["tight.on-demand"]["tpot_s"]
            / max(results["tight.spmoe"]["tpot_s"], 1e-12),
        "syncs_per_block_ample_spmoe":
            results["ample.spmoe"]["verify_syncs"]
            / max(results["ample.spmoe"]["verify_blocks"], 1),
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_path}", file=sys.stderr)


def sessions_micro(out_path: str = "BENCH_sessions.json"):
    """Multi-session serving micro-benchmark: the same request batch decoded
    serially (submit one after another) vs concurrently (Engine.serve
    round-robin, one verify block per session per turn) on the SAME warm
    spmoe engine, written to ``out_path`` so the scheduler's throughput
    trajectory is tracked PR over PR.

    The concurrent schedule batches every round's ready verify blocks into
    ONE fused cross-session dispatch (still lossless — asserted below), so
    the structural metrics to track PR over PR are ``launches_per_round``
    (= 1 on the all-hit path; was one per session) and ``syncs_per_block``
    (2/N per fused round vs 2 serial), with
    ``throughput_ratio_concurrent_vs_serial`` >= its PR-4 value (1.13) now
    that each round pays one dispatch + 2 syncs instead of N dispatches +
    2·N syncs.  Best-of-5 for both schedules (min wall) keeps the CPU
    wall-clock noise out of the ratio.
    """
    import jax
    from repro.configs.registry import get_config
    from repro.core.engine import Engine, EngineConfig, Request

    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    n_tokens, n_requests, conc = 24, 2, 2
    slots = cfg.num_moe_layers * cfg.num_experts       # ample: fast path
    prompts = [jax.random.randint(jax.random.PRNGKey(2 + i), (1, 8), 0,
                                  cfg.vocab_size) for i in range(n_requests)]

    def reqs():
        return [Request(prompt=p, max_new_tokens=n_tokens,
                        request_id=f"req-{i}")
                for i, p in enumerate(prompts)]

    config = EngineConfig(model=cfg, decode="sd", offload="spmoe",
                          cache_slots=slots, draft_len=4, max_seq=96)
    results = {}
    with Engine(config) as eng:
        # warm: compiles fast+slow verify paths for both schedules' shapes
        # and fills the expert cache
        for r in reqs():
            eng.submit(r)
        eng.serve_all(reqs(), concurrency=conc)

        best = {}
        for _ in range(5):           # best-of-5: the two schedules run the
            # identical per-session device work, so more trials converge the
            # ratio to its structural value instead of CPU scheduling jitter
            t0 = time.perf_counter()
            serial = [eng.submit(r) for r in reqs()]
            w_serial = time.perf_counter() - t0
            rt = eng.runtime
            vr0, rl0 = rt.verify_rounds, rt.round_launches
            t0 = time.perf_counter()
            conc_res = eng.serve_all(reqs(), concurrency=conc)
            w_conc = time.perf_counter() - t0
            rounds = rt.verify_rounds - vr0
            launches = rt.round_launches - rl0
            # interleaving must be lossless vs the serial schedule
            assert [r.tokens for r in serial] == [r.tokens for r in conc_res]
            if "serial" not in best or w_serial < best["serial"][0]:
                best["serial"] = (w_serial, serial, None, None)
            if "concurrent" not in best or w_conc < best["concurrent"][0]:
                best["concurrent"] = (w_conc, conc_res, rounds, launches)

    for sched, (wall, rs, rounds, launches) in best.items():
        total_tokens = sum(len(r.tokens) for r in rs)
        syncs = sum(r.metrics.verify_syncs for r in rs)
        blocks = sum(r.metrics.verify_blocks for r in rs)
        results[sched] = {
            "wall_s": wall,
            "throughput_tok_s": total_tokens / wall,
            "verify_syncs": syncs,
            "verify_blocks": blocks,
            "syncs_per_block": syncs / max(blocks, 1),
            "fast_blocks": sum(r.metrics.fast_blocks for r in rs),
            "fast_fallbacks": sum(r.metrics.fast_fallbacks for r in rs),
        }
        if rounds is not None:       # batched-round accounting (concurrent
            results[sched].update({  # schedule only): 1 fused launch and
                "rounds": rounds,    # <=2 syncs per all-hit round
                "launches_per_round": launches / max(rounds, 1),
                "syncs_per_round": syncs / max(rounds, 1),
            })
        _row(f"sessions.{sched}", wall * 1e6,
             f"throughput_tok_s={results[sched]['throughput_tok_s']:.1f};"
             f"syncs_per_block={results[sched]['syncs_per_block']:.2f}")
    results["meta"] = {
        "model": "mixtral-8x7b.reduced", "draft_len": 4,
        "n_requests": n_requests, "n_tokens": n_tokens,
        "concurrency": conc, "cache_slots": slots,
        "lossless_vs_serial": True,        # asserted per trial above
        "throughput_ratio_concurrent_vs_serial":
            results["concurrent"]["throughput_tok_s"]
            / max(results["serial"]["throughput_tok_s"], 1e-12),
        "launches_per_round_concurrent":
            results["concurrent"]["launches_per_round"],
        "syncs_per_block_concurrent":
            results["concurrent"]["syncs_per_block"],
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_path}", file=sys.stderr)


def chaos_micro(out_path: str = "BENCH_chaos.json"):
    """Chaos-hardened serving micro-benchmark: throughput / TPOT degradation
    curve vs injected fault rate, written to ``out_path``.

    One spmoe engine per fault rate serves the SAME 8 requests at
    concurrency 8 under the seeded fault injector (core/chaos.py):
    transient fetch errors at the swept rate, staged-payload corruption,
    latency spikes, and periodic prefetch-worker kills at the nonzero rates
    (kill_worker_every=5 exhausts the restart budget mid-run, so the
    graceful-degradation ladder demonstrably engages — asserted via
    ``degraded_rounds > 0``).  Losslessness is the hard gate: every rate's
    token streams must be bit-identical to the fault-free baseline; the
    bench FAILS otherwise.  Resilience counters (retries, checksum
    quarantines, worker restarts, degraded rounds, io_errors) are recorded
    per rate so the degradation curve is auditable PR over PR.
    """
    import jax
    from repro.configs.registry import get_config
    from repro.core.chaos import ChaosConfig
    from repro.core.engine import Engine, EngineConfig, Request

    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    n_tokens, n_requests, conc = 16, 8, 8
    rates = (0.0, 0.05, 0.15, 0.30)
    slots = 2 * cfg.num_experts            # tight-ish: real I/O pressure
    prompts = [jax.random.randint(jax.random.PRNGKey(2 + i), (1, 8), 0,
                                  cfg.vocab_size) for i in range(n_requests)]

    def reqs():
        return [Request(prompt=p, max_new_tokens=n_tokens,
                        request_id=f"req-{i}")
                for i, p in enumerate(prompts)]

    tparams = dparams = None
    baseline_tokens = None
    results = {}
    for rate in rates:
        chaos = None
        if rate > 0:
            chaos = ChaosConfig(seed=7, fetch_error_rate=rate,
                                insert_error_rate=rate / 4,
                                corrupt_rate=rate / 2,
                                spike_rate=rate / 4, spike_s=0.002,
                                kill_worker_every=5)
        config = EngineConfig(model=cfg, decode="sd", offload="spmoe",
                              cache_slots=slots, draft_len=4, max_seq=96,
                              chaos=chaos, retry_backoff_s=0.001)
        with Engine(config, tparams, dparams) as eng:
            tparams, dparams = eng.tparams, eng.dparams    # share init
            eng.serve_all(reqs(), concurrency=conc)        # warm/compile
            t0 = time.perf_counter()
            res = eng.serve_all(reqs(), concurrency=conc)
            wall = time.perf_counter() - t0
            c = eng.runtime.counters()
            health = eng.runtime.health()
            injected = dict(eng.runtime.chaos.injected) \
                if eng.runtime.chaos is not None else {}
        tokens = [r.tokens for r in res]
        assert all(r.finish_reason == "length" for r in res), \
            [r.finish_reason for r in res]
        if baseline_tokens is None:
            baseline_tokens = tokens
        # the losslessness gate: injected faults may slow serving down,
        # they must NEVER change a committed token
        assert tokens == baseline_tokens, f"token drift at fault rate {rate}"
        if rate > 0:
            assert c["prefetch_retries"] > 0 or c["prefetch_errors"] > 0, c
            assert c["degraded_rounds"] > 0, c
        total_tokens = sum(len(t) for t in tokens)
        results[f"rate_{rate}"] = {
            "fault_rate": rate,
            "wall_s": wall,
            "throughput_tok_s": total_tokens / wall,
            "prefetch_errors": c["prefetch_errors"],
            "prefetch_retries": c["prefetch_retries"],
            "checksum_failures": c["checksum_failures"],
            "worker_restarts": c["worker_restarts"],
            "degraded_rounds": c["degraded_rounds"],
            "io_errors": c["io_errors"],
            "health": health,
            "injected": injected,
        }
        _row(f"chaos.rate{rate}", wall * 1e6,
             f"throughput_tok_s={results[f'rate_{rate}']['throughput_tok_s']:.1f};"
             f"retries={c['prefetch_retries']};"
             f"degraded_rounds={c['degraded_rounds']};health={health}")
    base_tp = results["rate_0.0"]["throughput_tok_s"]
    results["meta"] = {
        "model": "mixtral-8x7b.reduced", "draft_len": 4,
        "n_requests": n_requests, "n_tokens": n_tokens,
        "concurrency": conc, "cache_slots": slots,
        "lossless_vs_fault_free": True,    # asserted per rate above
        "degradation_curve": {
            f"rate_{r}": results[f"rate_{r}"]["throughput_tok_s"] / base_tp
            for r in rates},
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"# wrote {out_path}", file=sys.stderr)


def kernels_bench():
    """Pallas kernels, interpret-mode timing vs jnp oracle (CPU proxy —
    real perf comes from the §Roofline analysis)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref as R
    from repro.kernels.flash_attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 64), jnp.float32)
    for name, fn in (
        ("flash_interp", lambda: flash_attention(q, k, v, interpret=True)),
        ("jnp_ref", lambda: R.attention_ref(q, k, v)),
    ):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn())
        wall = (time.perf_counter() - t0) * 1e6 / 3
        _row(f"kernels.attention_128.{name}", wall, "allclose=see tests")


BENCHES = {
    "fig2": fig2_observations,
    "fig4": fig4_latency_split,
    "fig9": fig9_datasets,
    "fig10": fig10_models,
    "fig11": fig11_memory,
    "fig12": fig12_ablation,
    "fig13": fig13_draft_len,
    "fig14": fig14_cutoff,
    "table3": table3_hit_rate,
    "engine_real": engine_real,
    "kernels": kernels_bench,
    "offload": offload_micro,
    "sessions": sessions_micro,
    "chaos": chaos_micro,
}

# benches that write a JSON artifact (support --out)
_OUT_DEFAULT = {"offload": "BENCH_offload.json",
                "sessions": "BENCH_sessions.json",
                "chaos": "BENCH_chaos.json"}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    argv = sys.argv[1:]
    out_path = None
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if "--mode" in argv:                 # --mode X == positional X
        i = argv.index("--mode")
        argv = argv[:i] + [argv[i + 1]] + argv[i + 2:]
    which = argv or list(BENCHES)
    writers = [n for n in which if n in _OUT_DEFAULT]
    if out_path is not None and len(writers) != 1:
        sys.exit(f"--out covers exactly one artifact-writing bench, but the "
                 f"selection {which} includes {writers or 'none'}; pick one "
                 f"of --mode {'/'.join(_OUT_DEFAULT)}")
    print("name,us_per_call,derived")
    for name in which:
        if name in _OUT_DEFAULT:
            BENCHES[name](out_path or _OUT_DEFAULT[name])
        else:
            BENCHES[name]()


if __name__ == "__main__":
    main()
