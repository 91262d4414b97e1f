"""Operations, bytes, rooflines and utilisation against hand-worked values
at both configurations' published widths."""
import pytest

from perfbench import flops, moe_roofline, mfu_common, spec
from perfbench.rundata import RunData
from perfbench.trace import Op, Trace
from perfbench.window import Delivery, Window

PEAKS = flops.peaks("TPU v5 lite")


def cfg(name):
    return spec.load_json(spec.HERE / "configs" / f"{name}.json")["model"]


def test_peaks_are_published_and_an_unknown_chip_is_an_error():
    assert PEAKS["bf16_flops_per_s"] == 197e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,d,f", [("mixtral-8x7b", 4096, 14336),
                                      ("mixtral-8x22b", 6144, 16384)])
def test_expert_bytes_and_flops(name, d, f):
    # three d x f bf16 matrices; 2 flops per multiply-add in three matmuls
    assert flops.expert_bytes(cfg(name)) == 3 * d * f * 2
    assert flops.expert_flops(cfg(name)) == 2 * 3 * d * f
    assert flops.expert_bytes(cfg("mixtral-8x7b")) == 352_321_536
    assert flops.expert_bytes(cfg("mixtral-8x22b")) == 603_979_776


def test_forward_flops_by_hand_mixtral_8x7b():
    # attention projections 2*4096*(32+16)*128 + 2*32*128*4096, scores and
    # values 4*32*128 per context position, router 2*4096*8, two experts,
    # head 2*4096*32000
    c = cfg("mixtral-8x7b")
    base = 50_331_648 + 33_554_432 + 65_536 + 2 * 352_321_536 + 262_144_000
    assert flops.forward_flops(c, 0) == base == 1_050_738_688
    assert flops.forward_flops(c, 1000) == base + 16_384 * 1000


def test_forward_flops_by_hand_mixtral_8x22b():
    c = cfg("mixtral-8x22b")
    attn = 2 * 6144 * (48 + 16) * 128 + 2 * 48 * 128 * 6144
    base = attn + 2 * 6144 * 8 + 2 * 603_979_776 + 2 * 6144 * 32768
    assert flops.forward_flops(c, 0) == base == 1_786_871_808
    assert flops.forward_flops(c, 10) == base + 4 * 48 * 128 * 10


def test_cache_moe_least_time_decode_block_is_bound_by_bytes():
    c = cfg("mixtral-8x7b")
    # 5 tokens x top-2 over 6 distinct experts: 6 experts' weights plus
    # 5 rows in and out
    want = (6 * 352_321_536 + 2 * 5 * 4096 * 2) / 819e9
    assert flops.cache_moe_least_s(c, 10, 6, 5, PEAKS) == pytest.approx(want)
    assert want == pytest.approx(2.581209e-3, rel=1e-6)


def test_cache_moe_least_time_long_prompt_is_bound_by_flops():
    c = cfg("mixtral-8x7b")
    want = 3072 * 2 * 352_321_536 / 197e12
    assert flops.cache_moe_least_s(c, 6144, 8, 3072, PEAKS) == \
        pytest.approx(want)
    assert want == pytest.approx(10.988140e-3, rel=1e-6)


def run_data(name, counters, sizes, ops, deliveries, tokens, seconds=10.0):
    conf = spec.load_json(spec.HERE / "configs" / f"{name}.json")
    return RunData("cell", conf["model"], conf["serving"], PEAKS, sizes,
                   Window(0.0, seconds, deliveries, tokens), counters, [],
                   Trace({"/device:TPU:0": ops}, []), seconds)


def test_roofline_share_sums_prefill_and_decode_blocks():
    # one request: a 256-token prefill (routes to all 8 experts) and 3
    # decode blocks that looked up 6 + 5 + 7 = 18 distinct experts
    counters = {0: {"verify_blocks": 4, "lookups": 8 + 18}}
    c = cfg("mixtral-8x7b")
    pre = max(256 * 2 * 352_321_536 / 197e12,
              (8 * 352_321_536 + 2 * 256 * 4096 * 2) / 819e9)
    dec = (18 * 352_321_536 + 3 * 2 * 5 * 4096 * 2) / 819e9
    call = ' custom-call(), custom_call_target="tpu_custom_call"'
    ops = [Op("%cache_moe.2 = bf16[7,16,14336]{2,1,0}" + call, 0,
              10_000_000),
           Op("%cache_moe.3 = bf16[7,16,4096]{2,1,0}" + call, 0, 5_000_000),
           Op("%cache_moe.4 = bf16[7,16,4096]{2,1,0} fusion()", 0, 7),
           Op("%fusion.1 = bf16[5]{0} fusion()", 0, 99_000_000)]
    run = run_data("mixtral-8x7b", counters, [(256, 32)], ops, [], {})
    assert moe_roofline.least_s(run) == pytest.approx(pre + dec)
    assert moe_roofline.share(run) == pytest.approx(
        100 * (pre + dec) / 15e-3)
    assert flops.cache_moe_least_s(c, 10, 6, 5, PEAKS) > 0


def test_roofline_is_silent_without_kernel_events():
    run = run_data("mixtral-8x7b", {0: {"verify_blocks": 2, "lookups": 9}},
                   [(128, 8)], [Op("fusion", 0, 5)], [], {})
    assert moe_roofline.share(run) is None


def test_mfu_counts_each_token_at_its_context():
    c = cfg("mixtral-8x22b")
    ds = [Delivery(0, 1.0, 1), Delivery(0, 2.0, 2)]
    run = run_data("mixtral-8x22b", {}, [(100, 8)], [], ds, {0: [1, 2, 3]},
                   seconds=4.0)
    tot = sum(flops.forward_flops(c, 100 + j) for j in range(3))
    assert mfu_common.delivered(run, skip_first=False) == pytest.approx(
        100 * tot / 4.0 / 197e12)
    dec = sum(flops.forward_flops(c, 100 + j) for j in (1, 2))
    assert mfu_common.delivered(run, skip_first=True) == pytest.approx(
        100 * dec / 4.0 / 197e12)
