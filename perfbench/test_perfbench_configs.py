"""The benchmark's data: configurations at published widths, and every
name in BENCHMARK.json found as a file."""
import json

import pytest

from perfbench import spec, traffic

BENCH = spec.load_json(spec.ROOT / "BENCHMARK.json")
CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))
# published config.json key -> the program's ModelConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "moe_d_ff",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "num_local_experts": "num_experts",
          "num_experts_per_tok": "num_experts_per_tok",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps", "sliding_window": "sliding_window",
          "num_hidden_layers": "num_layers"}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_builds_the_programs_model_at_published_widths(name):
    conf = spec.load_json(spec.HERE / "configs" / f"{name}.json")
    target, draft = spec.model_configs(conf)
    pub = conf["published"]
    changed = [k for k, f in WIDTHS.items() if getattr(target, f) != pub[k]]
    assert changed == ["num_hidden_layers"]
    assert set(conf["reduced"]) == {"num_hidden_layers", "expert_pool_slots"}
    assert target.head_dim * target.num_heads == pub["hidden_size"]
    assert target.d_ff == pub["intermediate_size"]
    assert target.is_moe and target.dtype == pub["torch_dtype"]
    assert conf["serving"]["expert_pool_slots"] < target.num_experts
    # the draft keeps the target's embedding widths and depth
    assert (draft.d_model, draft.vocab_size, draft.num_layers) == \
        (target.d_model, target.vocab_size, target.num_layers)
    assert not draft.is_moe


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
