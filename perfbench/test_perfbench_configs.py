"""The benchmark's data: configurations at published widths, and every
name in BENCHMARK.json found as a file."""
import dataclasses
import json
import re

import pytest

from perfbench import spec, traffic

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))
# what a configuration may never cut (BENCHMARK.json's contract): hidden,
# intermediate, latent, head and projection sizes, and experts per token
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|_expand)$"
                   r"|^num_experts_per_tok$")
# the two Mixtral files as they were accepted: their cuts, and the published
# keys -> ModelConfig fields that their own maps have to hold at least
MIXTRAL_CUTS = {"num_hidden_layers", "expert_pool_slots"}
MIXTRAL_PAIRS = {("hidden_size", "d_model"), ("intermediate_size", "d_ff"),
                 ("intermediate_size", "moe_d_ff"),
                 ("num_attention_heads", "num_heads"),
                 ("num_key_value_heads", "num_kv_heads"),
                 ("num_local_experts", "num_experts"),
                 ("num_experts_per_tok", "num_experts_per_tok"),
                 ("vocab_size", "vocab_size"), ("rope_theta", "rope_theta"),
                 ("rms_norm_eps", "norm_eps"),
                 ("sliding_window", "sliding_window"),
                 ("num_hidden_layers", "num_layers"),
                 ("torch_dtype", "dtype")}


def mapped(conf):
    """(published key, ModelConfig field) pairs of the file's own map."""
    return [(k, f) for k, fs in conf["fields"].items()
            for f in ([fs] if isinstance(fs, str) else fs)]


def check_config(conf):
    """A configuration builds the program's model at the published values
    its own map names, except the keys it lists as cut."""
    target, draft = spec.model_configs(conf)
    pub, reduced, pairs = conf["published"], set(conf["reduced"]), mapped(conf)
    model_fields = {f.name for f in dataclasses.fields(target)}
    assert {f for _, f in pairs} <= model_fields
    changed = [k for k, f in pairs if getattr(target, f) != pub[k]]
    assert changed == [k for k, _ in pairs if k in reduced]
    assert reduced == set(conf["cuts"])
    assert reduced <= set(pub) | set(conf["serving"])
    assert {k for k in reduced if k in pub} <= set(conf["fields"])
    assert not [k for k in reduced if WIDTH.search(k)]
    if "head_dim" not in {f for _, f in pairs}:
        # the published config states none: hidden_size / heads
        assert target.head_dim * target.num_heads == target.d_model
    assert target.is_moe
    assert conf["serving"]["expert_pool_slots"] < \
        target.num_experts * target.num_moe_layers
    # the draft keeps the target's embedding widths and depth
    assert (draft.d_model, draft.vocab_size, draft.num_layers) == \
        (target.d_model, target.vocab_size, target.num_layers)
    assert not draft.is_moe
    return target


@pytest.mark.parametrize("name", CONFIGS)
def test_config_builds_the_programs_model_at_published_widths(name):
    path = spec.HERE / "configs" / f"{name}.json"
    conf = spec.load_json(path)
    check_config(conf)
    assert spec.reference_path(conf, str(path)).is_file()
    listed = {c["name"]: c for c in BENCH["configs"]}
    if name in listed:
        assert set(conf["reduced"]) == set(listed[name]["reduced"])
        assert conf["source"] == listed[name]["source"]
    if name.startswith("mixtral-"):
        assert set(conf["reduced"]) == MIXTRAL_CUTS
        assert MIXTRAL_PAIRS <= set(mapped(conf))


def deepseek_v2_lite(num_layers=5):
    """DeepSeek-V2-Lite as a configuration file would state it (the
    program's ``deepseek_v2_lite_16b`` at the published values of
    huggingface.co/deepseek-ai/DeepSeek-V2-Lite's config.json), one dense
    and four MoE layers deep; nothing of it is a file of the benchmark."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    # the program's module leaves norm_eps at ModelConfig's 1e-5
    model = dataclasses.asdict(dataclasses.replace(
        CONFIG, num_layers=num_layers, norm_eps=1e-6))
    draft = {"name": "deepseek-v2-lite-draft", "family": "dense",
             "num_layers": num_layers, "d_model": 2048, "num_heads": 16,
             "num_kv_heads": 16, "head_dim": 128, "d_ff": 10944,
             "vocab_size": 102400, "norm_eps": 1e-6, "dtype": "bfloat16"}
    return {
        "name": "deepseek-v2-lite",
        "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/"
                  "blob/main/config.json",
        "reference": "reference_mla",
        "fields": {"hidden_size": "d_model", "intermediate_size": "d_ff",
                   "moe_intermediate_size": "moe_d_ff",
                   "num_attention_heads": "num_heads",
                   "num_key_value_heads": "num_kv_heads",
                   "num_hidden_layers": "num_layers",
                   "n_routed_experts": "num_experts",
                   "n_shared_experts": "num_shared_experts",
                   "num_experts_per_tok": "num_experts_per_tok",
                   "first_k_dense_replace": "first_dense_layers",
                   "kv_lora_rank": "kv_lora_rank",
                   "qk_nope_head_dim": "head_dim",
                   "qk_rope_head_dim": "rope_head_dim",
                   "v_head_dim": "v_head_dim", "vocab_size": "vocab_size",
                   "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
                   "tie_word_embeddings": "tie_embeddings",
                   "torch_dtype": "dtype"},
        "published": {"hidden_size": 2048, "intermediate_size": 10944,
                      "moe_intermediate_size": 1408,
                      "num_attention_heads": 16, "num_key_value_heads": 16,
                      "num_hidden_layers": 27, "n_routed_experts": 64,
                      "n_shared_experts": 2, "num_experts_per_tok": 6,
                      "first_k_dense_replace": 1, "kv_lora_rank": 512,
                      "q_lora_rank": None, "qk_nope_head_dim": 128,
                      "qk_rope_head_dim": 64, "v_head_dim": 128,
                      "vocab_size": 102400, "rope_theta": 10000,
                      "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
                      "torch_dtype": "bfloat16"},
        "model": model, "draft": draft,
        "serving": {"decode": "sd", "offload": "spmoe", "draft_len": 4,
                    "expert_pool_slots": 96},
        "reduced": ["num_hidden_layers", "expert_pool_slots"],
        "cuts": {"num_hidden_layers": "27 -> 5: the dense layer and four "
                                      "MoE layers",
                 "expert_pool_slots": "96 slots for 4 x 64 experts"},
    }


def test_a_second_architecture_is_checked_by_its_own_key_map():
    """MLA, shared experts and a leading dense layer pass the same checks
    as Mixtral, from the file's own map, with no edit of this test."""
    conf = deepseek_v2_lite()
    target = check_config(conf)
    assert target.use_mla and target.num_shared_experts == 2
    assert (target.first_dense_layers, target.num_moe_layers) == (1, 4)
    # a width moved from the published value is caught, cut or not
    for bad in ({"moe_d_ff": 1024}, {"kv_lora_rank": 256}):
        wrong = json.loads(json.dumps(conf))
        wrong["model"].update(bad)
        with pytest.raises(AssertionError):
            check_config(wrong)
    wrong = json.loads(json.dumps(conf))
    wrong["reduced"].append("moe_intermediate_size")
    wrong["cuts"]["moe_intermediate_size"] = "1408 -> 1024"
    wrong["model"]["moe_d_ff"] = 1024
    with pytest.raises(AssertionError):
        check_config(wrong)


def test_benchmark_names_resolve_to_files():
    for c in BENCH["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == 1
        assert traffic.sizes(cell.traffic)
        assert cell.checks["wide_gap"] > 0
    for m in BENCH["per_layer"]:
        assert (spec.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_each_cell_reports_what_its_layer_metrics_move():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_traffic_sizes_do_not_depend_on_the_seed():
    tr = spec.load_json(spec.HERE / "traffic" / "decode-c1.json")
    s = traffic.sizes(tr)
    assert s == traffic.sizes(json.loads(json.dumps(tr)))
    outs = [n for _, n in s]
    assert min(outs) >= 8 and max(outs) <= 32
    assert sorted(outs)[len(outs) // 2] in range(15, 18)
    # every round of three requests holds each prompt bucket once, and the
    # first answers spread over the distribution: median, then quartiles
    for i in range(0, len(s) - 2, 3):
        assert {p for p, _ in s[i:i + 3]} == {128, 256, 512}
    assert outs[:3] == [16, 11, 22]
    a = traffic.prompts(2**31 + 5, 0, [8, 8], 32000)
    b = traffic.prompts(2**31 + 5, 0, [8, 8], 32000)
    c = traffic.prompts(2**31 + 6, 0, [8, 8], 32000)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not all((x == y).all() for x, y in zip(a, c))
