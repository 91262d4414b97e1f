"""The harness takes its architecture from the configuration: the reference
module a file names, that module's weight rules, and the blocks the FLOP
count and the roofline see.  Mixtral reads exactly what it read before this
was so (values recorded from the harness as it stood then); a
DeepSeek-V2-Lite-shaped configuration, given here as a dict and a stand-in
reference module, passes through with no file of the harness edited."""
import copy
import dataclasses
import hashlib
import json
import math
import shutil

import jax
import numpy as np
import pytest

from perfbench import flops, mfu_common, moe_roofline, run, spec, weights
from perfbench.rundata import RunData
from perfbench.test_perfbench_configs import deepseek_v2_lite
from perfbench.window import Delivery, Window

PEAKS = flops.peaks("TPU v5 lite")
CELL = "mixtral-8x7b.decode-c1"
CONTEXTS = (0, 1, 128, 511, 4096, 32767)
# recorded from the harness before configurations named their reference
PINNED_FLOPS = {
    "mixtral-8x7b": [1050738688, 1050755072, 1052835840, 1059110912,
                     1117847552, 1587593216],
    "mixtral-8x22b": [1786871808, 1786896384, 1790017536, 1799430144,
                      1887535104, 2592153600]}
PINNED_ROOFLINE_MFU = {
    "mixtral-8x7b": (0.05206413096947497, 0.0005878986720812183,
                     0.0004810117847715736),
    "mixtral-8x22b": (0.08925027961904762, 0.000999533365279188,
                      0.0008178056966497461)}
PINNED_WEIGHTS = {
    "target": "d289ed6968dd3284d737f02b28c1e08ccffd0ea6d26a5b90c81f5f072844670d",
    "draft": "9f18c67f8945228034ce3284aca838349b30389fc35c65c3adf30195a8453ebd"}

# a stand-in for the reference module an MLA configuration would bring: its
# weight rules, and readings that put every served token far off
MLA_REFERENCE = '''
import numpy as np

FAN_IN = {"wdkv": "d_model", "wkr": "d_model", "wuk": "kv_lora_rank",
          "wuv": "kv_lora_rank", "wo": "num_heads*v_head_dim"}


def forward(w, cfg, tokens, rows, quant=""):
    raise NotImplementedError


def readings(w, cfg, prompt, served, controls=()):
    n = len(served)
    return {"gap": np.full(n, 1.0), "best": np.zeros(n),
            "margin": np.ones(n)}
'''


def model_block(name):
    return spec.load_json(spec.HERE / "configs" / f"{name}.json")


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def window_run(conf, cfg):
    """The counters and deliveries of a small window, on ``cfg``."""
    counters = {0: {"verify_blocks": 5, "lookups": 8 + 27},
                1: {"verify_blocks": 1, "lookups": 8},
                2: {"verify_blocks": 0, "lookups": 0},
                3: {"verify_blocks": 12, "lookups": 8 + 70}}
    sizes = [(128, 16), (256, 11), (512, 22), (128, 32)]
    ds = [Delivery(0, 1.0, 1), Delivery(0, 2.0, 4), Delivery(3, 2.5, 1),
          Delivery(3, 3.0, 5)]
    win = Window(0.0, 10.0, ds, {0: list(range(5)), 3: list(range(6))})
    return RunData("cell", cfg, conf["serving"], PEAKS, sizes, win,
                   counters, [], None, 10.0)


@pytest.fixture
def here(tmp_path, monkeypatch):
    """A copy of the benchmark's data files, read in place of the real
    ones, with the stand-in MLA reference beside them."""
    for d in ("configs", "traffic", "checks"):
        shutil.copytree(spec.HERE / d, tmp_path / d)
    (tmp_path / "reference_mla.py").write_text(MLA_REFERENCE)
    monkeypatch.setattr(spec, "HERE", tmp_path)
    return tmp_path


# --- Mixtral reads what it read before -------------------------------------

@pytest.mark.parametrize("name", sorted(PINNED_FLOPS))
def test_mixtral_flops_roofline_and_mfu_are_unchanged(name):
    conf = model_block(name)
    cfg = conf["model"]
    assert [flops.forward_flops(cfg, c) for c in CONTEXTS] == \
        PINNED_FLOPS[name]
    run_ = window_run(conf, cfg)
    assert (moe_roofline.least_s(run_),
            mfu_common.delivered(run_, skip_first=False),
            mfu_common.delivered(run_, skip_first=True)) == \
        PINNED_ROOFLINE_MFU[name]


def test_mixtral_weights_are_drawn_bit_for_bit_as_before():
    """The tiny Mixtral cell's weights, through the reference its file
    names, are the draws the harness made before."""
    from repro.models.registry import build_model
    conf = copy.deepcopy(model_block("mixtral-8x7b"))
    for part in ("model", "draft"):
        conf[part].update(d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=32000)
    conf["model"]["moe_d_ff"] = 64
    ref = spec.load_reference(spec.reference_path(conf, "mixtral-8x7b"))
    fan_in = getattr(ref, "FAN_IN", {})
    target, draft = spec.model_configs(conf)
    key = weights.seed_key(2**31 + 7)
    got = {"target": digest(weights.make(build_model(target),
                                         jax.random.fold_in(key, 0), fan_in)),
           "draft": digest(weights.make(build_model(draft),
                                        jax.random.fold_in(key, 1), fan_in))}
    assert got == PINNED_WEIGHTS


# --- a DeepSeek-V2-Lite-shaped configuration --------------------------------

def tiny_deepseek():
    """The program's DeepSeek-V2-Lite with widths shrunk: MLA (latent 32,
    nope 16, rope 16, v 36), one dense layer of 128, two MoE layers of 16
    experts top-6 and 2 shared, each 64 wide."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    return CONFIG.reduced(num_layers=3, num_experts=16,
                          num_experts_per_tok=6, num_shared_experts=2,
                          kv_lora_rank=32, rope_head_dim=16, v_head_dim=36)


def test_deepseek_weights_follow_the_rules_its_reference_declares(here):
    from repro.models.registry import build_model
    cfg = tiny_deepseek()
    assert (cfg.first_dense_layers, cfg.d_model, cfg.head_dim) == (1, 64, 16)
    conf = dict(deepseek_v2_lite(), model=dataclasses.asdict(cfg))
    ref = spec.load_reference(spec.reference_path(conf, "deepseek-v2-lite"))
    model = build_model(cfg)
    with pytest.raises(ValueError, match="wdkv"):
        weights.make(model, weights.seed_key(5))
    w = weights.make(model, weights.seed_key(5), ref.FAN_IN)
    # the declared std of each leaf: fan-in d 64, latent 32, H * v 144,
    # dense width 128, one routed expert 64, two shared 128
    declared = {
        "['wte']": 1.0, "['head']": 1 / 8,
        "['attn']['wq']": 1 / 8, "['attn']['wdkv']": 1 / 8,
        "['attn']['wkr']": 1 / 8, "['attn']['wuk']": 1 / math.sqrt(32),
        "['attn']['wuv']": 1 / math.sqrt(32), "['attn']['wo']": 1 / 12,
        "['ffn']['wg']": 1 / 8, "['ffn']['wu']": 1 / 8,
        "['ffn']['wd']": 1 / math.sqrt(128),
        "['moe']['gate']": 1 / 8, "['moe']['wg']": 1 / 8,
        "['moe']['wu']": 1 / 8, "['moe']['wd']": 1 / 8,
        "['moe']['shared']['wg']": 1 / 8, "['moe']['shared']['wu']": 1 / 8,
        "['moe']['shared']['wd']": 1 / math.sqrt(128)}
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        p = jax.tree_util.keystr(path)
        a = np.asarray(leaf, np.float32)
        if path[-1].key in weights.NORMS:
            assert (a == 1).all(), p
            continue
        rule = next(k for k in declared if p.endswith(k))
        seen.add(rule)
        assert a.std() == pytest.approx(declared[rule], rel=0.1), p
    assert seen == set(declared)
    assert {jax.tree_util.keystr(p[:1]) for p, _ in
            jax.tree_util.tree_flatten_with_path(w)[0]} >= \
        {"['dense_layers']", "['layers']"}


def test_deepseek_forward_flops_by_hand():
    cfg = dataclasses.asdict(tiny_deepseek())
    # MLA, non-absorbed: q 2*64*4*(16+16), kv_a 2*64*(32+16),
    # kv_b 2*32*4*(16+36), out 2*4*36*64; a position: scores 2*4*32,
    # values 2*4*36
    attn = 16_384 + 6_144 + 13_312 + 18_432
    per_position = 256 + 288
    dense = 2 * 3 * 64 * 128
    # router 2*64*16, 6 routed + 2 shared experts of 2*3*64*64
    moe = 2_048 + 8 * 24_576
    head = 2 * 64 * 256
    base = 3 * attn + 1 * dense + 2 * moe + head
    assert base == 642_048
    assert flops.forward_flops(cfg, 0) == base
    assert flops.forward_flops(cfg, 1000) == base + 3 * per_position * 1000
    # the count is the published form's whichever form the program decodes
    # in; a compressed query is not counted rather than counted wrong
    with pytest.raises(ValueError):
        flops.forward_flops(dict(cfg, q_lora_rank=16), 0)


def test_deepseek_roofline_counts_only_its_moe_layers():
    cfg = dataclasses.asdict(tiny_deepseek())
    counters = {0: {"verify_blocks": 3, "lookups": 2 * 16 + 20}}
    run_ = RunData("cell", cfg, {"draft_len": 4}, PEAKS, [(16, 8)],
                   Window(0.0, 1.0, [], {}), counters, [], None, 1.0)
    # two MoE layers; a 16-token prefill routes to all 16 experts of each,
    # then two blocks of 5 tokens x top-6 that looked up 20 experts
    pre = flops.cache_moe_least_s(cfg, 16 * 6 * 2, 2 * 16, 16 * 2, PEAKS)
    dec = flops.cache_moe_least_s(cfg, 2 * 5 * 6 * 2, 20, 2 * 5 * 2, PEAKS)
    assert moe_roofline.least_s(run_) == pre + dec
    every_layer = flops.cache_moe_least_s(cfg, 16 * 6 * 3, 3 * 16, 16 * 3,
                                          PEAKS)
    assert every_layer > pre


# --- the reference lookup ---------------------------------------------------

def test_the_check_runs_the_reference_its_configuration_names(here):
    """A cell whose configuration names the stand-in reads the stand-in's
    gaps: every served token wide."""
    path = here / "configs" / "mixtral-8x7b.json"
    conf = spec.load_json(path)
    conf["reference"] = "reference_mla"
    path.write_text(json.dumps(conf))
    cell = spec.load_cell(CELL)
    assert cell.reference == here / "reference_mla.py"
    ref = spec.load_reference(cell.reference)
    win = Window(0.0, 1.0, [], {0: [5, 6, 7], 1: [8]})
    prompts = [np.zeros((1, 4), np.int32)] * 2
    ok, checks, lines, _ = run.check(ref, None, cell, prompts, win, 1)
    assert not ok and checks["wide_gaps"]["value"] == 4, lines


@pytest.mark.parametrize("reference,rc", [
    (None, 2), ("no_such_reference", 2), ("../run", 2), ("reference", 3)])
def test_run_refuses_a_configuration_without_its_reference(
        here, capsys, reference, rc):
    """No default: a file without ``"reference"``, or naming no module of
    the benchmark, exits 2 naming the file before any device work; a file
    that names one goes on to look for the chip (3 here)."""
    shutil.copy(spec.ROOT / "perfbench" / "reference.py", here)
    path = here / "configs" / "mixtral-8x7b.json"
    conf = spec.load_json(path)
    conf.pop("reference")
    if reference is not None:
        conf["reference"] = reference
    path.write_text(json.dumps(conf))
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds",
                     "1"]) == rc
    out, err = capsys.readouterr()
    assert out == ""
    if rc == 2:
        assert str(path) in err
