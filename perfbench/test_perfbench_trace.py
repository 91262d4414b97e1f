"""Trace reduction on a small synthetic trace, and on one the profiler
records here."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import trace
from perfbench.trace import Op, Trace


def synthetic():
    dev = [Op("fusion.1", 0, 100), Op("_gate_up_kernel", 50, 100),
           Op("_down_kernel", 400, 100), Op("fusion.1", 1000, 50)]
    host = [Op("PjitFunction(verify)", 140, 300), Op("python", 0, 2000),
            Op("wait", 520, 400)]
    return Trace({"/device:TPU:0": dev}, host)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_is_the_union_of_device_ops():
    # [0,150) + [400,500) + [1000,1050)
    assert trace.busy_s(synthetic()) == pytest.approx(300e-9)


def test_top_ops_sums_by_short_name():
    assert trace.short("%cache_moe.2 = bf16[7,16]{2,1,0} custom-call(x)") \
        == "%cache_moe.2 = bf16[7,16]"
    assert trace.top_ops(synthetic().ops(), 2) == [
        ("fusion.1", pytest.approx(150e-9)),
        ("_gate_up_kernel", pytest.approx(100e-9))]


def test_idle_gaps_are_named_by_the_host_event_that_overlaps_most():
    gaps = trace.idle_gaps(synthetic(), 2)
    # [500,1000) overlaps "wait" 400 ns and "python" 500 ns: python wins;
    # [150,400) overlaps PjitFunction 250 ns and python 250 ns: the shorter
    assert gaps == [("python", pytest.approx(500e-9)),
                    ("PjitFunction(verify)", pytest.approx(250e-9))]


def test_a_recorded_trace_loads(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    assert tr.host                      # the runtime's host events
    assert trace.busy_s(tr) >= 0.0
