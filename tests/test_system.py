"""End-to-end system tests: training convergence, checkpoint/restart
equivalence, fault-tolerant supervision with elastic re-planning, and the
full SP-MoE serving path."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_draft_for
from repro.configs.base import RunConfig, ShapeConfig
from repro.configs.registry import get_config, get_draft_config
from repro.core.engine import Engine, EngineConfig, Request
from repro.core.sd import greedy_generate
from repro.launch.train import Trainer
from repro.models.registry import build_model


def _tiny_trainer(ckpt_dir=None, arch="llama3.2-3b", grad_compress=False):
    cfg = get_config(arch).reduced(num_layers=2, d_model=32, num_heads=2,
                                   num_kv_heads=2, head_dim=16, d_ff=64,
                                   vocab_size=128)
    shape = ShapeConfig("tiny", 32, 4, "train")
    run = RunConfig(warmup_steps=2, total_steps=40, learning_rate=3e-3)
    return Trainer(cfg, shape, run, ckpt_dir=ckpt_dir,
                   grad_compress=grad_compress), cfg


def test_training_loss_decreases():
    tr, _ = _tiny_trainer()
    _, losses = tr.train(25, log_every=0)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.1, (first, last)


@pytest.mark.slow      # training soaks: tier-1 only, not API signal
def test_checkpoint_restart_resumes_identically():
    """Train 10 straight vs train 5 + restart + 5: identical params (data
    pipeline is restart-stable, checkpoint is exact)."""
    with tempfile.TemporaryDirectory() as d1:
        tr, _ = _tiny_trainer()
        state_a, _ = tr.train(10, log_every=0)
        tr2, _ = _tiny_trainer(ckpt_dir=d1)
        tr2.train(5, ckpt_every=5, log_every=0)
        tr2.ckpt.wait()
        tr3, _ = _tiny_trainer(ckpt_dir=d1)
        state_b, _ = tr3.train(10, log_every=0)
    for a, b in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(state_b["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


@pytest.mark.slow
def test_training_with_grad_compression_converges():
    tr, _ = _tiny_trainer(grad_compress=True)
    _, losses = tr.train(25, log_every=0)
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


@pytest.mark.slow
def test_supervised_training_with_failure_and_restart():
    """Injected failure mid-run; restart restores from checkpoint and
    completes the remaining steps."""
    with tempfile.TemporaryDirectory() as d:
        tr, _ = _tiny_trainer(ckpt_dir=d)
        with pytest.raises(RuntimeError):
            tr.train(20, ckpt_every=4, fail_at=9, log_every=0)
        tr.ckpt.wait()
        assert tr.ckpt.latest_step() == 8
        tr2, _ = _tiny_trainer(ckpt_dir=d)
        _, losses = tr2.train(20, ckpt_every=4, log_every=0)
        assert len(losses) == 12              # resumed from step 8


def test_spmoe_serving_end_to_end():
    """Full paper pipeline on a reduced mixtral through the unified request
    API: draft -> predict -> prefetch -> cached verification; lossless
    output + prefetching active."""
    cfg = get_config("mixtral-8x7b").reduced(dtype="float32")
    dcfg = make_draft_for(cfg)
    target = build_model(cfg)
    tparams = target.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size)
    ref = greedy_generate(target, tparams, prompt, 16, 64)
    config = EngineConfig(model=cfg, draft=dcfg, decode="sd", offload="spmoe",
                          cache_slots=8, draft_len=4, max_seq=64)
    with Engine(config, tparams) as eng:
        res = eng.submit(Request(prompt=prompt, max_new_tokens=16))
    assert res.tokens == ref.tolist()
    stats = res.metrics
    assert stats["prefetched"] > 0
    assert 0 <= stats["hit_rate"] <= 1
    assert stats["cutoff_layer"] >= 0


@pytest.mark.parametrize("layers", [None, 2], ids=["reduced", "depth-2"])
def test_serving_model_pair(layers):
    """The serving pair: CPU-reduced float32 by default; with a depth cut,
    target and draft keep their published widths and dtype, are cut to the
    same depth, and every cut is listed."""
    from repro.launch.serve import model_pair
    cfg, dcfg, cuts = model_pair("mixtral-8x7b", layers)
    target, draft = get_config("mixtral-8x7b"), get_draft_config("mixtral-8x7b")
    if layers is None:
        assert cfg == target.reduced(dtype="float32")
        assert dcfg == draft.reduced(dtype="float32")
    else:
        assert cfg == dataclasses.replace(target, num_layers=layers)
        assert dcfg == dataclasses.replace(draft, num_layers=layers)
        assert all(f"-> {layers}" in c for c in cuts)
    assert len(cuts) == 2
    with pytest.raises(ValueError):
        model_pair("mixtral-8x7b", target.num_layers + 1)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The environment's cache directory wins and is left to JAX; otherwise
    the cache goes to the checkout's fixed ``.jax_cache``."""
    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = CC.enable_compile_cache()
        assert path == str(CC.CHECKOUT / ".jax_cache")
        assert (CC.CHECKOUT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
