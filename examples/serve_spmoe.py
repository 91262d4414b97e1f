"""End-to-end serving driver: compares the four offloading policies (the
paper's frameworks) on the same reduced MoE model + prompt set through the
unified request API (one Engine per policy serving all requests against a
warm expert cache), reporting per-policy hit rate / prefetch / eviction
stats and validating that every policy emits the identical (lossless)
token stream.  With ``--concurrency > 1`` the requests are decoded
concurrently — the round-robin session scheduler interleaves one verify
block per session per turn on the shared cache, and the losslessness
column must stay True.

    PYTHONPATH=src python examples/serve_spmoe.py [--arch deepseek-v2-lite-16b]
    PYTHONPATH=src python examples/serve_spmoe.py --requests 3 --concurrency 3
"""
import argparse

import jax

from repro.configs.registry import get_config
from repro.core.engine import (Engine, EngineConfig, OffloadPolicy, Request,
                               derive_draft_config)
from repro.core.sd import greedy_generate
from repro.models.registry import build_model

OFFLOAD_POLICIES = [p.value for p in OffloadPolicy if p != OffloadPolicy.NONE]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--tokens", type=int, default=20)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--cache-slots", type=int, default=8)
    ap.add_argument("--concurrency", type=int, default=1,
                    help="sessions decoded concurrently per engine "
                         "(round-robin on the shared cache; 1 = serial)")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced(dtype="float32")
    assert cfg.is_moe, "pick an MoE arch"
    dcfg = derive_draft_config(cfg)
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    dparams = build_model(dcfg).init(jax.random.PRNGKey(1))

    prompts = [jax.random.randint(jax.random.PRNGKey(10 + i), (1, 8), 0,
                                  cfg.vocab_size)
               for i in range(args.requests)]
    refs = [greedy_generate(target, tparams, p, args.tokens, 64).tolist()
            for p in prompts]

    print(f"{'policy':14s} {'lossless':9s} {'hit_rate':9s} {'prefetched':11s} "
          f"{'on_demand':10s} {'evict':6s}")
    for policy in OFFLOAD_POLICIES:
        config = EngineConfig(model=cfg, draft=dcfg, decode="sd",
                              offload=policy, cache_slots=args.cache_slots,
                              draft_len=4, max_seq=64)
        with Engine(config, tparams, dparams) as eng:
            reqs = [Request(prompt=p, max_new_tokens=args.tokens)
                    for p in prompts]
            if args.concurrency > 1:
                results = eng.serve_all(reqs, concurrency=args.concurrency)
            else:
                results = [eng.submit(r) for r in reqs]
            ok = all(res.tokens == ref for res, ref in zip(results, refs))
            m = eng.metrics()    # cumulative across the request stream
        print(f"{policy:14s} {str(ok):9s} {m.hit_rate:9.2%} "
              f"{m.prefetched:<11d} {m.on_demand_loads:<10d} "
              f"{m.evictions:<6d}")


if __name__ == "__main__":
    main()
