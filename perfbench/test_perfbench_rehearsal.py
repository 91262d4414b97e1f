"""A CPU rehearsal of one cell's run at a tiny size, with the harness's look
for a chip steered here in the test: the run is correct as served, not
correct when a served token is altered, and the float8 control reads above
the cell's limit.  Without a chip the real entry point prints no
result and fails."""
import copy
import os
import shutil
import subprocess
import sys

import jax
import pytest

from perfbench import flops, run, spec

CELL = "mixtral-8x7b.decode-c1"


def tiny_cell():
    cell = copy.deepcopy(spec.load_cell(CELL))
    for part in ("model", "draft"):
        m = cell.config[part]
        m.update(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                 d_ff=128, vocab_size=32000)
        if "moe_d_ff" in m:
            m["moe_d_ff"] = 64
    # answers short enough that a loaded CPU completes one in the window
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
    # no persistent compile cache written from a test process
    mp.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    mp.setattr(run, "require_accelerator", lambda chips: jax.devices())
    peaks = flops.peaks("TPU v5 lite")
    mp.setattr(flops, "peaks", lambda kind: peaks)
    yield cell
    mp.undo()


@pytest.fixture(scope="module")
def served(on_cpu):
    """One warm run of the tiny cell: (result, check lines, readings)."""
    return run.run_cell(args(2**31 + 7), log=lambda s: None)


def args(seed, seconds=3.0):
    return run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", str(seconds)])


def test_cpu_rehearsal_of_a_cell_is_correct(served):
    out, lines, _ = served
    assert out["correct"], lines
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert {"tokens_per_s", "tpot_ms", "gap_p95_ms", "setup_s"} <= \
        set(out["metrics"])
    assert list(out)[-1] == "checks"
    assert out["checks"]["wide_gaps"]["value"] == 0
    assert lines[-2].startswith("check wide_gaps")


def test_a_token_altered_where_it_is_produced_is_not_correct(on_cpu,
                                                             monkeypatch):
    from repro.core.engine import Engine
    serve = Engine.serve

    def altered(self, requests, **kw):
        for i, (rid, tok) in enumerate(serve(self, requests, **kw)):
            yield rid, (tok + 1) % 32000 if i == 2 else tok

    monkeypatch.setattr(Engine, "serve", altered)
    out, lines, _ = run.run_cell(args(3), log=lambda s: None)
    assert not out["correct"], lines
    assert out["checks"]["wide_gaps"]["value"] > \
        out["checks"]["wide_gaps"]["limit"]


def test_the_float8_control_reads_wider_than_the_limit(on_cpu, served):
    """The control at a test size, over a fixed number of tokens: the
    reference's own greedy continuation of three served prompts (what a
    sound program serves), so the reading does not depend on how many
    tokens the CPU served in its window."""
    import numpy as np
    from perfbench.gaps import summary
    _, _, read = served
    # the module the run's check used is the one the configuration names
    reference = read["reference"]
    assert reference.__file__ == str(spec.reference_path(on_cpu.config, CELL))
    assert on_cpu.reference.name == "reference.py"
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


def test_no_result_without_a_chip_or_without_the_program(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable, "perfbench/run.py", "--workload", CELL,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=spec.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 3 and p.stdout == "", p.stderr
    # a directory holding only BENCHMARK.json and the benchmark's files
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == "", p.stderr
