"""DeepSeek-V2-Lite as the program serves it, against the plain reference
its configuration names (``reference_mla``); the configuration file against
the program's module; and the readers of the cell's new metrics.

The system comparison drives a small DeepSeek-shaped model (1 dense + 2 MoE
layers, 16 experts top-6, 2 shared, MLA latent 32 / rope 16, YaRN factor
40 over the published 4096 original positions, raw top-k weights, expert
width 48, no
multiple of a kernel block) through ``Engine`` (``sd``, ``spmoe``) on seeded
weights from ``weights.make``, with a pool of 31 slots: fewer than the 32
routed experts, more than a block's 4 x 6 choices.  Every verify block's
logits (the prefill block, then decode blocks through the cache, on the
fast and on the slow path) are compared with the reference's full forward
over the committed tokens and the block.

Tolerance: both sides compute in float32, so they differ only by the order
of float32 sums (absorbed latent decode against expanded attention, grouped
expert sums against a loop over experts): under 3e-6 on logits of size ~4
here.  1e-4 leaves thirty times that, and each variant of the model that
drops a published part (renormalised top-k, no latent norm, no YaRN) misses
by far more."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import moe_roofline, run, spec, weights
from perfbench.rundata import RunData
from perfbench.trace import Op, Trace
from perfbench.window import Window

ATOL = 1e-4
CONF = spec.load_json(spec.HERE / "configs" / "deepseek-v2-lite.json")
REF = spec.load_reference(spec.reference_path(CONF, "deepseek-v2-lite"))


def small_deepseek(**over):
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    return CONFIG.reduced(num_layers=3, num_experts=16, num_experts_per_tok=6,
                          num_shared_experts=2, kv_lora_rank=32,
                          rope_head_dim=16, moe_d_ff=48, dtype="float32",
                          **over)


@pytest.fixture(scope="module")
def model():
    from repro.core.engine import derive_draft_config
    from repro.models.registry import build_model
    cfg = small_deepseek()
    assert (cfg.norm_topk_prob, cfg.rope_scaling_factor, cfg.norm_eps) == \
        (False, 40.0, 1e-6)
    dcfg = derive_draft_config(cfg)
    key = weights.seed_key(2**31 + 5)
    tp = weights.make(build_model(cfg), jax.random.fold_in(key, 0),
                      REF.FAN_IN)
    dp = weights.make(build_model(dcfg), jax.random.fold_in(key, 1),
                      REF.FAN_IN)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 20), 0,
                                           cfg.vocab_size))
    return cfg, dcfg, tp, dp, prompt


def serve_blocks(model, monkeypatch, cfg, new_tokens):
    """Serve the prompt through Engine; -> (committed tokens, [(block
    tokens, position, logits, fast?)]).  Every block tries the fast path
    first, so the blocks whose experts were all resident commit the fast
    path's logits and the others the slow path's."""
    from repro.core.engine import Engine, EngineConfig, Request
    from repro.core.runtime import OffloadEngine
    _, dcfg, tp, dp, prompt = model
    blocks = []
    verify = OffloadEngine._verify_block

    def spied(self, tokens, pos, tcache):
        self._st.fast_ok = True
        before = self.fast_blocks
        logits, tcache = verify(self, tokens, pos, tcache)
        blocks.append((np.asarray(tokens)[0].tolist(), pos,
                       np.asarray(logits)[0], self.fast_blocks > before))
        return logits, tcache

    monkeypatch.setattr(OffloadEngine, "_verify_block", spied)
    config = EngineConfig(model=cfg, draft=dcfg, decode="sd", offload="spmoe",
                          draft_len=3, cache_slots=31, max_seq=128,
                          prefetch_mode="vanilla")
    with Engine(config, tp, dp) as eng:
        res = eng.submit(Request(prompt=jnp.asarray(prompt),
                                 max_new_tokens=new_tokens))
    monkeypatch.undo()
    return prompt[0].tolist() + res.tokens, blocks


def worst_error(model, committed, blocks):
    """The largest |served - reference| logit over every block's rows."""
    cfg, _, tp, _, _ = model
    ref_cfg = dataclasses.asdict(cfg)
    worst = 0.0
    for toks, pos, logits, _ in blocks:
        tokens = np.asarray(committed[:pos] + toks, np.int32)
        want, _ = REF.forward(tp, ref_cfg, tokens,
                              np.arange(pos, pos + len(toks)))
        worst = max(worst, float(np.abs(want - logits).max()))
    return worst


def test_prefill_and_decode_on_both_paths_match_the_reference(model,
                                                              monkeypatch):
    cfg = model[0]
    committed, blocks = serve_blocks(model, monkeypatch, cfg, 24)
    assert blocks[0][1] == 0 and len(blocks[0][0]) == 20       # prefill
    decode = blocks[1:]
    assert any(fast for *_, fast in decode), "no block took the fast path"
    assert any(not fast for *_, fast in decode), "no slow decode block"
    assert worst_error(model, committed, blocks) < ATOL


@pytest.mark.parametrize("variant", ["renormalised", "no_latent_norm",
                                     "no_yarn"])
def test_a_model_without_a_published_part_misses_the_reference(
        model, monkeypatch, variant):
    from repro.models import layers
    cfg = model[0]
    if variant == "renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif variant == "no_yarn":
        cfg = dataclasses.replace(cfg, rope_scaling_factor=1.0)
    else:
        latent = layers.mla_latent

        def unnormalised(p, x, positions, c):
            _, k_rope = latent(p, x, positions, c)
            return jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), k_rope

        monkeypatch.setattr(layers, "mla_latent", unnormalised)
    committed, blocks = serve_blocks(model, monkeypatch, cfg, 4)
    assert worst_error(model, committed, blocks) > 100 * ATOL


# --- the configuration file and the program's module -----------------------

def test_the_config_file_and_the_programs_module_agree():
    """Every published width and routing setting the program's
    ``deepseek_v2_lite_16b`` states is the file's, the nested YaRN group
    included; the file's top level holds the published config as run.  The
    published values the program and the reference hold as constants, or
    leave out as no-ops, are what the file states: a configuration with
    others fails here and needs a field of its own."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    from repro.models import layers
    model = CONF["model"]
    module = dataclasses.asdict(CONFIG)
    differ = sorted(k for k in model
                    if k not in ("name", "num_layers") and
                    model[k] != module[k])
    assert differ == []
    pub = CONF["published"]
    for key, field in CONF["rope_scaling_fields"].items():
        assert model[field] == pub["rope_scaling"][key]
        assert CONF["draft"][field] == model[field]
    yarn = pub["rope_scaling"]
    assert yarn["type"] == "yarn"
    assert {k: yarn[k] for k in REF.YARN} == REF.YARN == {
        "original_max_position_embeddings": layers.YARN_ORIGINAL_MAX_POSITION,
        "beta_fast": layers.YARN_BETA_FAST,
        "beta_slow": layers.YARN_BETA_SLOW}
    assert yarn["mscale"] == yarn["mscale_all_dim"]     # cos/sin factor 1
    assert pub["routed_scaling_factor"] == 1
    catalog = {k: v for k, v in pub.items() if k != "torch_dtype"}
    assert {k: CONF[k] for k in catalog} == \
        dict(catalog, num_hidden_layers=model["num_layers"])
    assert CONF["serving"]["expert_pool_slots"] == 128


# --- the cell's new readers --------------------------------------------------

def run_data(tr, counters, window_ns=2000, cfg=None, sizes=None):
    return RunData("cell", cfg or {}, {"draft_len": 4}, {}, sizes or [],
                   Window(0.0, 1.0, [], {}), counters, [], tr,
                   window_ns / 1e9)


def predict_trace():
    """A 2000 ns window.  Device busy [100,200) and [1500,1600).  A draft
    [0,1000) holding predictions [200,400) and [600,700); a verify
    [1000,2000)."""
    dev = [Op("fusion.1", 100, 100), Op("fusion.2", 1500, 100)]
    host = [Op("spmoe.draft", 0, 1000), Op("spmoe.predict", 200, 200),
            Op("spmoe.predict", 600, 100), Op("spmoe.verify", 1000, 1000)]
    return Trace({"/device:TPU:0": dev}, host)


def test_idle_predict_share_owns_the_predictions_inside_a_draft():
    tr = predict_trace()
    rd = run_data(tr, {})
    # prediction owns 300 ns, all idle, of the 2000 ns window
    assert run.load_reader("idle_predict_share")(rd) == \
        pytest.approx(100 * 300 / 2000)
    # the draft's own share reads as it would without the predictions
    without = Trace(tr.devices, [o for o in tr.host
                                 if o.name != "spmoe.predict"])
    assert run.load_reader("idle_draft_share")(rd) == \
        run.load_reader("idle_draft_share")(run_data(without, {})) == \
        pytest.approx(100 * 900 / 2000)
    # a program without the span, or a trace without a device plane
    assert run.load_reader("idle_predict_share")(
        run_data(without, {})) is None
    assert run.load_reader("idle_predict_share")(
        run_data(Trace({}, tr.host), {})) is None


def test_compact_mb_per_token_by_hand_and_none_without_the_counter():
    counters = {0: {"tokens": 10, "compact_bytes": 30_000_000},
                1: {"tokens": 20, "compact_bytes": 60_000_000}}
    read = run.load_reader("compact_mb_per_token")
    assert read(run_data(None, counters)) == pytest.approx(3.0)
    old = {0: {"tokens": 10}, 1: {"tokens": 20}}
    assert read(run_data(None, old)) is None


# the compiled program's names, as the profiler gives its TPU events:
# cache_moe's Pallas stages, a compaction loop, the pool copied in column
# pieces, the gathered stack, a slot-scatter loop and a slice of the
# stages' [M, C, f] output
KERNEL_GU = ('%cache_moe.2 = bf16[30,16,1408]{2,1,0} custom-call(%x), '
             'custom_call_target="tpu_custom_call"')
KERNEL_DN = ('%cache_moe.3 = bf16[30,16,2048]{2,1,0} custom-call(%h), '
             'custom_call_target="tpu_custom_call"')
GATHER_LOOP = ('%while.55 = (s32[]{:T(128)}, bf16[128,1408,256]{2,1,0:T(8,128)'
               '(2,1)}, s32[30,1]{0,1:T(1,128)S(1)}, bf16[30,1408,256]{2,1,0:'
               'T(8,128)(2,1)S(1)}, s32[]{:T(128)}) while(%tuple.421), '
               'condition=%wide.while_cond.22')
POOL_COPY = ('%fusion.35 = (bf16[128,1408,256]{2,1,0:T(8,128)(2,1)}, '
             'bf16[128,1408,256]{2,1,0:T(8,128)(2,1)}) fusion(%wd.1), '
             'kind=kLoop')
STACK = ('%broadcast_select_fusion.4 = bf16[30,1408,2048]{2,1,0:T(8,128)(2,1)}'
         ' fusion(%f.16, %c.16), kind=kLoop')
SCATTER_LOOP = ('%while.4 = (s32[]{:T(128)}, bf16[128,2048,1408]{2,1,0}, '
                'bf16[8,2048,1408]{2,1,0}) while(%tuple.44)')
STAGE_SLICE = ('%slice-start.4 = ((bf16[30,16,1408]{2,1,0}), bf16[8,16,1408]'
               '{2,1,0}, s32[]{:S(2)}) slice-start(%cache_moe.2)')


def top6_run(ops):
    cfg = CONF["model"]
    counters = {0: {"verify_blocks": 3, "lookups": 4 * 64 + 60}}
    return RunData("cell", cfg, CONF["serving"], spec.load_json(
        spec.HERE / "peaks.json")["TPU v5 lite"], [(128, 16)],
        Window(0.0, 1.0, [], {}), counters, [],
        Trace({"/device:TPU:0": ops}, []), 1.0)


def test_the_top6_roofline_counts_compaction_with_the_kernel():
    """The Pallas stages (3 ms) and compaction's loop, pool copy and
    gathered stack (2 + 4 + 1 ms, the loop's body event inside it counted
    once) are the denominator; the slot scatter and a slice of the stages'
    output are not.  The Pallas stages alone read as ``.decode`` does."""
    ms = 1_000_000
    kernel = [Op(KERNEL_GU, 0, 2 * ms), Op(KERNEL_DN, 2 * ms, ms)]
    compaction = [Op(GATHER_LOOP, 10 * ms, 2 * ms),
                  Op("%broadcast.204.clone = bf16[30,1408,256]{2,1,0} "
                     "broadcast(%constant.39)", 11 * ms, ms // 2),
                  Op(POOL_COPY, 20 * ms, 4 * ms), Op(STACK, 30 * ms, ms)]
    other = [Op(SCATTER_LOOP, 40 * ms, 5 * ms),
             Op(STAGE_SLICE, 50 * ms, ms),
             Op("%fusion.9 = bf16[30,2048,2048]{2,1,0} fusion()", 60 * ms,
                ms)]
    rd = top6_run(kernel + compaction + other)
    least = moe_roofline.least_s(rd)
    assert run.load_reader("cache_moe_roofline.top6")(rd) == \
        pytest.approx(100 * least / 10e-3)
    assert run.load_reader("cache_moe_roofline.decode")(rd) == \
        pytest.approx(100 * least / 3e-3)
    # a program that compacts nothing reads its Pallas stages alone
    assert run.load_reader("cache_moe_roofline.top6")(
        top6_run(kernel + other)) == pytest.approx(100 * least / 3e-3)
    assert run.load_reader("cache_moe_roofline.top6")(
        top6_run(other)) is None


def test_compaction_is_told_apart_only_where_the_pool_outgrows_a_block():
    top6 = spec.load_module(spec.HERE / "metrics" /
                            "cache_moe_roofline.top6.py", "top6_reader")
    ops = [Op(n, 0, 1) for n in (GATHER_LOOP, POOL_COPY, STACK, SCATTER_LOOP,
                                 STAGE_SLICE, KERNEL_GU, KERNEL_DN)]
    got = top6.compaction_ops(ops, 128, 30, 2048, 1408)
    assert [o.name for o in got] == [GATHER_LOOP, POOL_COPY, STACK]
    # Mixtral's 7-slot pool holds no more than a block's 10 choices
    assert top6.compaction_ops(ops, 7, 7, 4096, 14336) == []
    assert top6.split_hlo(GATHER_LOOP)[1] == "while"
    assert top6.split_hlo(STACK) == (
        "bf16[30,1408,2048]{2,1,0:T(8,128)(2,1)}", "fusion")


# --- the rule against the program the TPU compiler makes ---------------------

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, not attached: programs compile, nothing runs."""
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def entry_ops(compiled):
    """The entry computation's instructions of a compiled program, as
    trace events named by their HLO text."""
    text = compiled.as_text()
    body = text[text.index("\nENTRY"):].splitlines()[1:]
    return [Op(line.strip().removeprefix("ROOT "), 0, 1) for line in body
            if " = " in line]


def test_the_compaction_rule_matches_the_compiled_program(one_chip):
    """cache_moe at this cell's shapes compiled for a v5e: in a decode
    block (T=5, 30 of 128 slots) the rule takes every loop and fusion of
    compaction's weight gather and none of the Pallas stages; in a
    512-token prefill block and in the slot scatter it takes nothing."""
    from repro.core.cache import _batched_insert
    from repro.kernels.cache_moe import cache_moe
    top6 = spec.load_module(spec.HERE / "metrics" /
                            "cache_moe_roofline.top6.py", "top6_reader")
    cfg = CONF["model"]
    d, f, k = cfg["d_model"], cfg["moe_d_ff"], cfg["num_experts_per_tok"]
    S = CONF["serving"]["expert_pool_slots"]
    bf16 = jnp.bfloat16

    def arg(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def moe_ops(T):
        return entry_ops(jax.jit(cache_moe).lower(
            arg((T, d)), arg((T, k), jnp.int32), arg((T, k)),
            arg((S, d, f)), arg((S, f, d)), arg((S, d, f))).compile())

    decode = moe_ops(5)
    got = top6.compaction_ops(decode, S, 30, d, f)
    kinds = {top6.split_hlo(o.name)[1] for o in got}
    assert kinds == {"while", "fusion", "broadcast"}
    # the gather's own loops and fusions that write expert weights
    gathers = [o for o in decode if 'op_name="jit(cache_moe)/jit(_take)/'
               'gather"' in o.name and top6.split_hlo(o.name)[1] in
               ("while", "fusion") and f in {
                   int(n) for a in top6.ARRAY.findall(
                       top6.split_hlo(o.name)[0]) for n in a}]
    assert gathers and all(o in got for o in gathers)
    assert not [o for o in got if "_take" not in o.name and
                "op_name" in o.name]
    assert not set(map(id, got)) & set(map(id, moe_roofline.kernel_ops(
        decode)))
    assert top6.compaction_ops(moe_ops(512), S, 30, d, f) == []
    rows = {"wg": (8, d, f), "wu": (8, d, f), "wd": (8, f, d)}
    scatter = entry_ops(jax.jit(_batched_insert, donate_argnums=(0,)).lower(
        {n: arg((S,) + r[1:]) for n, r in rows.items()},
        {n: arg(r) for n, r in rows.items()}, arg((8,), jnp.int32)).compile())
    assert [o for o in scatter if top6.split_hlo(o.name)[1] == "while"]
    assert top6.compaction_ops(scatter, S, 30, d, f) == []
