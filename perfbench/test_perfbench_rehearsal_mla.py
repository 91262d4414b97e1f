"""A CPU rehearsal of ``deepseek-v2-lite.decode-c1`` at a tiny size, with the
harness's look for a chip steered here in the test: the run is correct as
served, not correct when a served token is altered, and the float8 control
reads above the cell's limit.  Routing keeps its published shape (64
experts, top-6, 2 shared, a dense layer first, YaRN, raw top-k weights);
only the widths shrink."""
import copy

import jax
import numpy as np
import pytest

from perfbench import flops, run, spec
from perfbench.gaps import summary

CELL = "deepseek-v2-lite.decode-c1"
NEW = ("cache_moe_roofline.top6", "compact_mb_per_token",
       "idle_predict_share")


def tiny_cell():
    cell = copy.deepcopy(spec.load_cell(CELL))
    for part in ("model", "draft"):
        cell.config[part].update(d_model=64, num_heads=4, num_kv_heads=4,
                                 head_dim=16, d_ff=128, kv_lora_rank=32,
                                 rope_head_dim=16, v_head_dim=16)
    cell.config["model"]["moe_d_ff"] = 48
    cell.traffic.update(prompt_tokens=[16], warmup_output_tokens=6,
                        output_tokens={"lognormal_median": 6,
                                       "lognormal_sigma": 0.5, "min": 4,
                                       "max": 10})
    return cell


@pytest.fixture(scope="module")
def on_cpu():
    from repro.launch import compile_cache
    mp = pytest.MonkeyPatch()
    cell = tiny_cell()
    mp.setattr(spec, "load_cell", lambda name: cell)
    mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    mp.setattr(run, "require_accelerator", lambda chips: jax.devices())
    peaks = flops.peaks("TPU v5 lite")
    mp.setattr(flops, "peaks", lambda kind: peaks)
    yield cell
    mp.undo()


def args(seed, seconds=3.0):
    return run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", str(seconds)])


@pytest.fixture(scope="module")
def served(on_cpu):
    return run.run_cell(args(2**31 + 11), log=lambda s: None)


def test_cpu_rehearsal_of_the_mla_cell_is_correct(served, on_cpu):
    out, lines, read = served
    assert out["correct"], lines
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert {"tokens_per_s", "tpot_ms", "setup_s"} <= set(out["metrics"])
    # the cell reports no metric scoped to Mixtral's cell alone
    assert "gap_p95_ms" not in out["metrics"]
    assert out["checks"]["wide_gaps"]["value"] == 0
    assert read["reference"].__file__.endswith("reference_mla.py")
    assert {m["name"] for m in on_cpu.per_layer} >= set(NEW)


def test_an_altered_token_is_not_correct_in_the_mla_cell(on_cpu,
                                                         monkeypatch):
    from repro.core.engine import Engine
    serve = Engine.serve
    vocab = on_cpu.config["model"]["vocab_size"]

    def altered(self, requests, **kw):
        for i, (rid, tok) in enumerate(serve(self, requests, **kw)):
            yield rid, (tok + 1) % vocab if i == 2 else tok

    monkeypatch.setattr(Engine, "serve", altered)
    out, lines, _ = run.run_cell(args(5), log=lambda s: None)
    assert not out["correct"], lines
    assert out["checks"]["wide_gaps"]["value"] > \
        out["checks"]["wide_gaps"]["limit"]


def test_the_float8_control_of_the_mla_cell_reads_wider(on_cpu, served):
    """Over the reference's own greedy continuation of three served prompts
    (what a sound program serves), so the reading does not depend on how
    many tokens the CPU served in its window."""
    _, _, read = served
    reference = read["reference"]
    cfg = on_cpu.config["model"]
    gaps, cgaps, margins = [], [], []
    for p in read["prompts"][:3]:
        toks = list(p[0])
        for _ in range(48):
            lg, _ = reference.forward(read["tparams"], cfg, np.asarray(toks),
                                      np.asarray([len(toks) - 1]))
            toks.append(int(lg[0].argmax()))
        r = reference.readings(read["tparams"], cfg, p[0].tolist(),
                               toks[p.shape[1]:], controls=("fp8",))
        gaps.append(r["gap"])
        cgaps.append(r["fp8_gap"])
        margins.append(r["margin"])
    ck = on_cpu.checks
    m, wide = ck["min_router_margin"], ck["wide_gap"]
    sound = summary(gaps, margins, m, wide)
    control = summary(cgaps, margins, m, wide)
    assert sound["gap_max"] == 0.0 and sound["wide_gaps"] == 0
    assert control["wide_gaps"] > ck["wide_gaps"]
