"""Serving driver for the unified request-level API (core/engine.py).

Policy is two-axis: ``--decode`` picks how tokens are committed (greedy |
sd | sd-adaptive), ``--offload`` picks where expert weights live (none |
spmoe | adapmoe | moe-infinity | on-demand).  Any combination is valid and
lossless; offload policies require an MoE target.  The legacy single-axis
``--policy`` flag is kept as a deprecated alias (``sd-only`` ->
``--decode sd --offload none``, ``spmoe`` -> ``--decode sd --offload
spmoe``, ...).

One Engine serves all ``--requests`` requests, so request 2+ hits a warm
expert cache (watch ``hit_rate`` climb).  ``--concurrency N`` decodes up
to N requests at once on that one warm cache — each scheduling round
batches the ready sessions' verify blocks into ONE fused kernel launch
(one routing pass, ≤2 verify syncs per round instead of 2 per session), and
every stream stays bit-identical to serving it alone.  ``--stream`` prints
tokens as each verify block commits (prefixed with the request id when
concurrent); ``--stop-token`` ends a request early on every decode x
offload combination identically.

Chaos hardening: ``--chaos`` turns on the seeded fault injector
(core/chaos.py) against the expert I/O plane — transient fetch/insert
errors, latency spikes, payload corruption, prefetch-worker kills — tuned
with the ``--chaos-*`` rates.  Serving stays lossless (retry +
checksum-quarantine + the graceful-degradation ladder absorb every injected
fault); the per-request report grows the resilience counters
(``prefetch_errors`` / ``prefetch_retries`` / ``checksum_failures`` /
``worker_restarts`` / ``degraded_rounds`` / ``io_errors``) and the footer
prints the engine's final health.  ``--deadline-s`` arms a per-request
wall-clock budget (``finish_reason="deadline"`` when it expires).

Model size: by default the target and its draft are the CPU-sized
``reduced`` pair (d_model 64, float32).  ``--layers N`` instead serves the
published widths in the config's own dtype with both models cut to N
layers — the size to run on an accelerator.  The first lines printed list
every cut.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --decode sd --offload spmoe --tokens 32 --requests 2

    # published Mixtral widths, one layer, on the chip
    PYTHONPATH=src python -m repro.launch.serve --layers 1 --cache-slots 7 \
        --prompt-len 16 --tokens 16 --requests 4

    # four requests, two decoded concurrently per turn
    PYTHONPATH=src python -m repro.launch.serve --requests 4 --concurrency 2

    # chaos drill: 10% fetch faults + corruption + worker kills, still lossless
    PYTHONPATH=src python -m repro.launch.serve --requests 4 --concurrency 2 \
        --chaos --chaos-fetch-error-rate 0.1 --chaos-corrupt-rate 0.05 \
        --chaos-kill-every 5
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.core.chaos import ChaosConfig
from repro.configs.registry import get_config, get_draft_config
from repro.core.engine import (DECODE_POLICIES, OFFLOAD_POLICIES, Engine,
                               EngineConfig, Request, derive_draft_config)
from repro.launch.compile_cache import enable_compile_cache

# legacy --policy values -> (decode, offload)
LEGACY_POLICY = {
    "greedy": ("greedy", "none"),
    "sd-only": ("sd", "none"),
    "sd-adaptive": ("sd-adaptive", "none"),
    "spmoe": ("sd", "spmoe"),
    "adapmoe": ("sd", "adapmoe"),
    "moe-infinity": ("sd", "moe-infinity"),
    "on-demand": ("sd", "on-demand"),
}


def model_pair(arch: str, layers: Optional[int] = None
               ) -> Tuple[ModelConfig, ModelConfig, List[str]]:
    """(target, draft, cuts) for serving ``arch``.

    ``layers=None`` gives the CPU-sized pair: both configs ``reduced`` to
    d_model 64 in float32.  Otherwise both keep their published widths and
    dtype and are cut to ``layers`` layers alike.  The draft is the
    registry's pairing for ``arch`` (Mixtral's is Mistral-7B), or the
    engine's derived dense sibling where the registry has none.  ``cuts``
    names every way the pair differs from the published configs."""
    target = get_config(arch)
    draft = get_draft_config(arch)
    if layers is None:
        cfg = target.reduced(dtype="float32")
        dcfg = draft.reduced(dtype="float32") if draft is not None \
            else derive_draft_config(cfg)
        return cfg, dcfg, [f"reduced widths (d_model {cfg.d_model}, "
                           f"{cfg.num_layers} layers, vocab "
                           f"{cfg.vocab_size})", "dtype float32"]
    if not target.first_dense_layers < layers <= target.num_layers:
        raise ValueError(f"--layers must be in ({target.first_dense_layers}, "
                         f"{target.num_layers}] for {arch}")
    cfg = dataclasses.replace(target, num_layers=layers)
    dcfg = dataclasses.replace(draft, num_layers=layers) \
        if draft is not None else derive_draft_config(cfg)
    dcut = (f"draft {draft.name}: num_layers {draft.num_layers} -> {layers}"
            if draft is not None else
            f"draft {dcfg.name}: derived from the cut target")
    return cfg, dcfg, [f"target {target.name}: num_layers "
                       f"{target.num_layers} -> {layers}", dcut]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the published widths cut to this many "
                         "layers (default: the CPU-sized reduced pair)")
    ap.add_argument("--decode", default=None, choices=DECODE_POLICIES,
                    help="token-commit policy (default: sd)")
    ap.add_argument("--offload", default=None, choices=OFFLOAD_POLICIES,
                    help="expert-weight policy (default: spmoe for MoE)")
    ap.add_argument("--policy", default=None, choices=sorted(LEGACY_POLICY),
                    help="DEPRECATED single-axis alias for --decode/--offload")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--concurrency", type=int, default=1,
                    help="requests decoded concurrently on the one warm "
                         "cache (1 = serial).  Each scheduling round "
                         "batches the ready sessions' verify blocks into "
                         "ONE fused kernel launch — one routing pass and "
                         "<=2 verify syncs per round instead of per session "
                         "— while every stream stays bit-identical to "
                         "serving it alone")
    ap.add_argument("--draft-len", type=int, default=4)
    ap.add_argument("--cache-slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--stop-token", type=int, action="append", default=None)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as verify blocks commit")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget; an expired request "
                         "retires with finish_reason=deadline")
    chz = ap.add_argument_group(
        "chaos", "seeded fault injection against the expert I/O plane "
                 "(lossless by construction: retries, checksum quarantine "
                 "and the degradation ladder absorb every injected fault)")
    chz.add_argument("--chaos", action="store_true",
                     help="enable the fault injector (core/chaos.py)")
    chz.add_argument("--chaos-seed", type=int, default=0)
    chz.add_argument("--chaos-fetch-error-rate", type=float, default=0.1,
                     help="P(transient error) per HostExpertStore.fetch")
    chz.add_argument("--chaos-insert-error-rate", type=float, default=0.0,
                     help="P(transient error) per ExpertCache.insert")
    chz.add_argument("--chaos-spike-rate", type=float, default=0.0,
                     help="P(latency spike) per fetch")
    chz.add_argument("--chaos-spike-ms", type=float, default=10.0,
                     help="latency-spike duration (milliseconds)")
    chz.add_argument("--chaos-corrupt-rate", type=float, default=0.0,
                     help="P(staged-payload byte flip) per fetch — caught "
                          "by checksum verification, never inserted")
    chz.add_argument("--chaos-kill-every", type=int, default=0,
                     help="kill the prefetch worker every Nth task "
                          "(0 = never); the supervisor restarts it")
    args = ap.parse_args()

    decode, offload = args.decode, args.offload
    if args.policy is not None:
        if decode or offload:
            ap.error("--policy is an alias; don't mix with --decode/--offload")
        decode, offload = LEGACY_POLICY[args.policy]
        print(f"# --policy {args.policy} is deprecated; use "
              f"--decode {decode} --offload {offload}")
    cfg, dcfg, cuts = model_pair(args.arch, args.layers)
    for cut in cuts:
        print(f"# cut: {cut}")
    enable_compile_cache()
    if decode is None:
        decode = "sd"
    if offload is None:
        offload = "spmoe" if cfg.is_moe else "none"

    chaos = None
    if args.chaos:
        chaos = ChaosConfig(
            seed=args.chaos_seed,
            fetch_error_rate=args.chaos_fetch_error_rate,
            insert_error_rate=args.chaos_insert_error_rate,
            spike_rate=args.chaos_spike_rate,
            spike_s=args.chaos_spike_ms / 1e3,
            corrupt_rate=args.chaos_corrupt_rate,
            kill_worker_every=args.chaos_kill_every)
    max_seq = args.prompt_len + args.tokens + max(args.draft_len, 8) + 8
    config = EngineConfig(model=cfg, draft=dcfg, decode=decode,
                          offload=offload, cache_slots=args.cache_slots,
                          draft_len=args.draft_len, max_seq=max_seq,
                          chaos=chaos)
    prompts = [jax.random.randint(jax.random.PRNGKey(2 + i),
                                  (1, args.prompt_len), 0, cfg.vocab_size)
               for i in range(args.requests)]
    reqs = [Request(prompt=prompt, max_new_tokens=args.tokens,
                    stop_tokens=args.stop_token or (),
                    deadline_s=args.deadline_s,
                    request_id=f"req-{i}")
            for i, prompt in enumerate(prompts)]

    def report(res):
        print(f"[{res.request_id}] finish={res.finish_reason}")
        for k, v in sorted(res.metrics.as_dict().items()):
            print(f"    {k}: {v}")

    with Engine(config) as eng:
        if args.concurrency > 1:
            if args.stream:
                for rid, tok in eng.serve(reqs, concurrency=args.concurrency):
                    print(f"{rid}:{tok}", end=" ", flush=True)
                print()
                results = eng.last_batch
            else:
                results = eng.serve_all(reqs, concurrency=args.concurrency)
            for res in results:
                if not args.stream:
                    print(f"[{res.request_id}] tokens: {res.tokens}")
                report(res)
        else:
            for req in reqs:
                if args.stream:
                    print(f"[{req.request_id}] tokens:", end=" ", flush=True)
                    for tok in eng.stream(req):
                        print(tok, end=" ", flush=True)
                    print()
                    res = eng.last_result
                else:
                    res = eng.submit(req)
                    print(f"[{req.request_id}] tokens: {res.tokens}")
                report(res)
        cum = eng.metrics()
        print(f"cumulative: requests={cum.requests} tokens={cum.tokens} "
              f"hit_rate={cum.hit_rate:.3f}")
        if eng.runtime is not None:
            # runtime counters, not the Metrics ledger: worker-thread
            # increments landing between turn windows still show up here
            c = eng.runtime.counters()
            print(f"health: {eng.runtime.health()} "
                  f"(prefetch_errors={c['prefetch_errors']} "
                  f"retries={c['prefetch_retries']} "
                  f"checksum_failures={c['checksum_failures']} "
                  f"worker_restarts={c['worker_restarts']} "
                  f"degraded_rounds={c['degraded_rounds']} "
                  f"io_errors={c['io_errors']})")
            if args.chaos and eng.runtime.chaos is not None:
                inj = eng.runtime.chaos.injected
                print("chaos injected:", " ".join(
                    f"{k}={v}" for k, v in sorted(inj.items())))


if __name__ == "__main__":
    main()
