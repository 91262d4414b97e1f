"""Run one benchmark cell once on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix, each
a file under ``perfbench/``.  One run:

1. builds the configuration's ``EngineConfig`` -> ``Engine`` (speculative
   decoding, SP-MoE offload) with weights made on the device from the seed;
2. warms every shape the traffic uses by serving warm-up requests of each
   prompt length, in two passes, the first over both prefill paths;
3. drives ``Engine.serve`` for ``--seconds`` as a closed loop of the
   traffic's clients (one serving slot each), over a request list longer
   than the window can drain, stamping every token on the host clock;
4. reports the cell's end-to-end metrics (``--trace 0``) or, with the
   profiler on, its per-layer metrics (``--trace 1``), each per-layer
   metric read by ``metrics/<name>.py``;
5. checks what the window served against the plain float32 reference
   that the configuration names (``"reference"``: ``reference.py`` for
   Mixtral), once the program's state is freed.

The last line of standard output is one JSON object; the numbers compared
and their limits are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from perfbench import spec, window  # noqa: E402
from perfbench.gaps import summary  # noqa: E402

WARMUP_PASSES = 2


class NoAccelerator(RuntimeError):
    pass


def require_accelerator(chips: int):
    """The devices JAX runs on: TPU chips, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX runs on {devs[0].platform!r}, not on a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX has "
                            f"{len(devs)}")
    return devs


def load_reader(name: str):
    return spec.load_module(spec.HERE / "metrics" / f"{name}.py",
                            "perfbench.metrics." + name.replace(".", "_")).read


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def requests(Request, sizes, prompts) -> List[Any]:
    import jax.numpy as jnp
    return [Request(prompt=jnp.asarray(p), max_new_tokens=n,
                    request_id=str(i))
            for i, ((_, n), p) in enumerate(zip(sizes, prompts))]


def warm_up(engine, Request, cell, seed: int, vocab: int, clog, log):
    """Serve each prompt length ``clients`` times per pass, with fresh token
    ids, in a fixed number of passes (the same set-up in every run).

    The runtime tries a prompt on its fast verify path only while its
    engine-wide ``_fast_hint`` says the expert cache is warm, and compiles
    that path anew for each prompt length (ROADMAP A.7): left to chance,
    one compile took 38 s inside a window (measured on one v5e chip).  So the
    first pass serves each length once with the hint off and once with it
    on.  The hint is the runtime's private state and the program offers no
    public way to warm both paths; a runtime without it is an error here,
    since the warm-up would then leave a compile to the window unseen."""
    tr = cell.traffic
    clients = int(tr["clients"])
    out = int(tr["warmup_output_tokens"])
    from perfbench import traffic
    rt = engine.runtime
    if not hasattr(rt, "_fast_hint"):
        raise RuntimeError(
            "the runtime has no _fast_hint: the warm-up cannot compile both "
            "prefill paths; the benchmark's warm-up needs a new way to do so")
    seen, stream = set(), 100
    for p in range(WARMUP_PASSES):
        n0, t = clog.count, time.perf_counter()
        for b in tr["prompt_tokens"]:
            for hint in ((False, True) if p == 0 else (None,)):
                if hint is not None:
                    rt._fast_hint = hint
                lens = [int(b)] * clients
                stream += 1
                engine.serve_all(
                    requests(Request, [(n, out) for n in lens],
                             traffic.prompts(seed, stream, lens, vocab)),
                    concurrency=clients)
        names = {c[2] for c in clog.compiles[n0:]}
        log(f"warm-up pass {p}: {clog.count - n0} executables, new "
            f"functions {sorted(names - seen)}, "
            f"{time.perf_counter() - t:.3f} s")
        seen |= names


def choose_checked(win: window.Window, seed: int, most: int) -> List[int]:
    """Requests whose served tokens are checked: all that delivered in the
    window, or a sample drawn from the seed that holds the longest."""
    rids = sorted(win.tokens)
    if len(rids) <= most:
        return rids
    longest = max(rids, key=lambda r: (len(win.tokens[r]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = rng.choice(len(rest), size=most - 1, replace=False)
    return sorted([longest] + [rest[i] for i in pick])


def check(reference, tparams, cell, prompts, win, seed
          ) -> Tuple[bool, Dict, List[str], Dict[str, Any]]:
    """Compare the served tokens with the reference module ``reference``;
    -> (correct, checks, lines for standard error, the reference's
    readings)."""
    ck = cell.checks
    cfg = cell.config["model"]
    rids = choose_checked(win, seed, int(ck["max_requests"]))
    gaps, margins = [], []
    for rid in rids:
        r = reference.readings(tparams, cfg, prompts[rid][0].tolist(),
                               win.tokens[rid])
        gaps.append(r["gap"])
        margins.append(r["margin"])
    res = summary(gaps, margins, float(ck["min_router_margin"]),
                  float(ck["wide_gap"]))
    limit = int(ck["wide_gaps"])
    kept = res["tokens"] - res["near_ties"]
    ok = kept > 0 and res["wide_gaps"] <= limit
    checks = {"wide_gaps": {"value": res["wide_gaps"], "limit": limit},
              "tokens_checked": {"value": kept, "limit": 1}}
    lines = [f"checked {res['tokens']} served tokens of {len(rids)} requests "
             f"against the float32 reference; {res['near_ties']} at a "
             f"routing near-tie (margin < {ck['min_router_margin']}) left "
             f"out; mean gap {res['gap_mean']:.6g}, widest "
             f"{res['gap_max']:.6g} (over all {res['gap_max_all']:.6g})",
             f"check wide_gaps (gap > {ck['wide_gap']}): {res['wide_gaps']} "
             f"limit {limit}",
             f"check tokens_checked: {kept} limit >= 1"]
    return ok, checks, lines, dict(res, rids=rids)


def run_cell(args, log=print
             ) -> Tuple[Dict[str, Any], List[str], Dict[str, Any]]:
    """One run of one cell; -> (result line object, check lines, what the
    check read: its readings, the weights, prompts and window)."""
    cell = spec.load_cell(args.workload)
    devs = require_accelerator(cell.chips)
    import jax
    from perfbench import flops, trace as trace_mod, traffic, weights
    from perfbench.compile_log import CompileLog
    from perfbench.rundata import RunData
    from repro.core.engine import Engine, EngineConfig, Request
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import build_model

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    peaks = flops.peaks(dev.device_kind)
    log(f"device: {json.dumps(device)}; compile cache "
        f"{enable_compile_cache()}")
    clog = CompileLog()
    conf, tr = cell.config, cell.traffic
    serving = conf["serving"]
    cfg_t, cfg_d = spec.model_configs(conf)
    clients = int(tr["clients"])
    sizes = traffic.sizes(tr)
    vocab = cfg_t.vocab_size

    reference = spec.load_reference(cell.reference)
    fan_in = getattr(reference, "FAN_IN", {})

    t = time.perf_counter()
    key = weights.seed_key(args.seed)
    target, draft = build_model(cfg_t), build_model(cfg_d)
    tparams = weights.make(target, jax.random.fold_in(key, 0), fan_in)
    dparams = weights.make(draft, jax.random.fold_in(key, 1), fan_in)
    log(f"weights: {time.perf_counter() - t:.3f} s")
    prompts = traffic.prompts(args.seed, 0, [p for p, _ in sizes], vocab)
    reqs = requests(Request, sizes, prompts)
    max_seq = max(p for p, _ in sizes) + max(n for _, n in sizes) \
        + serving["draft_len"] + 2
    config = EngineConfig(model=cfg_t, draft=cfg_d, decode=serving["decode"],
                          offload=serving["offload"],
                          draft_len=serving["draft_len"],
                          cache_slots=serving["expert_pool_slots"],
                          max_seq=max_seq)
    t = time.perf_counter()
    engine = Engine(config, tparams, dparams)
    log(f"engine init: {time.perf_counter() - t:.3f} s")
    warm_up(engine, Request, cell, args.seed, vocab, clog, log)
    gc.collect()

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = time.time() - PROCESS_START
    stream = engine.serve(reqs, concurrency=clients)
    win = window.drive(stream, t0, args.seconds)
    stream.close()
    t_close = time.perf_counter()
    results = engine.last_batch
    if args.trace:
        jax.profiler.stop_trace()
    in_window = clog.between(t0, t_close)
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    log(f"window: {len(win.deliveries)} deliveries, "
        f"{sum(d.n for d in win.deliveries)} tokens, "
        f"{len(win.tokens)} requests; closed {t_close - win.t_end:.3f} s "
        f"after its end; {len(in_window)} executables obtained in it "
        f"{[c[2] for c in in_window][:8]}; set-up {setup_s:.3f} s "
        f"({clog.count} executables, "
        f"{clog.seconds:.3f} s, persistent cache hits {clog.cache_hits} "
        f"misses {clog.cache_misses})")

    max_new = [n for _, n in sizes]
    finish = {int(r.request_id): r.finish_reason for r in results}
    attempted, failed = window.outcome(win, max_new, finish)
    counters = {int(r.request_id): r.metrics.as_dict() for r in results
                if int(r.request_id) in win.tokens}

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not args.trace:
        values = {"tokens_per_s": window.tokens_per_s(win),
                  "tpot_ms": window.tpot_ms(win),
                  "gap_p95_ms": window.gap_p95_ms(win),
                  "ttft_ms": window.ttft_ms(win, max_new, clients),
                  "setup_s": setup_s}
        log("end-to-end: " + json.dumps(values))
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        trace = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = RunData(cell.name, conf["model"], serving, peaks, sizes, win,
                      counters, in_window, trace, t_close - t0)
        for m in cell.per_layer:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_s(trace)
        device["window_s"] = t_close - t0
        breakdown = {"device_ops": [list(x) for x in
                                    trace_mod.top_ops(trace.ops(), 10)],
                     "idle_gaps": [list(x) for x in
                                   trace_mod.idle_gaps(trace, 10)]}
        trace = run = None                 # the trace is large: drop it

    # free the program's state before the reference runs
    engine.close()
    del engine, stream, results, dparams, draft, reqs
    gc.collect()
    t = time.perf_counter()
    correct, checks, lines, read = check(reference, tparams, cell, prompts,
                                         win, args.seed)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out, lines, dict(read, reference=reference, tparams=tparams,
                            prompts=prompts, win=win)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        spec.load_reference(spec.load_cell(args.workload).reference)
        import repro.core.engine  # noqa: F401  the program under test
    except (OSError, KeyError, ValueError, ImportError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    try:
        out, lines, _ = run_cell(args,
                                 log=lambda s: print(s, file=sys.stderr))
    except NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
