"""Device-idle time attributed to the serving stages' spans, and the readers
of the spans and counters, on synthetic traces worked by hand."""
import threading

import jax
import jax.numpy as jnp
import pytest

from perfbench import run, spans, trace
from perfbench.rundata import RunData
from perfbench.trace import Op, Trace
from perfbench.window import Window

NEW = ("idle_load_share", "idle_draft_share", "idle_verify_share",
       "idle_prefill_share", "host_syncs_per_block")
WORKER = "PjitFunction(_batched_insert)"     # a prefetch worker's insert


def synthetic(devices=True):
    """A 2000 ns window.  Device busy [100,200) and [1100,1150).  Serving
    spans: prefill [0,1000) holding a load wave [300,600), which holds an
    insert [350,450); draft [1000,1200); verify [1200,1500) holding a load
    wave [1250,1300).  The prefetch worker's inserts [1400,1800) and
    [1850,1900).  Nothing serving is open over [1500,2000)."""
    dev = [Op("fusion.1", 100, 100), Op("%cache_moe.2", 1100, 50)]
    host = [Op("spmoe.prefill", 0, 1000), Op("spmoe.load_wave", 300, 300),
            Op(WORKER, 350, 100), Op("spmoe.draft", 1000, 200),
            Op("spmoe.verify", 1200, 300), Op("spmoe.load_wave", 1250, 50),
            Op(WORKER, 1400, 400), Op(WORKER, 1850, 50),
            Op("PjitFunction(verify_fast)", 1210, 20)]
    return Trace({"/device:TPU:0": dev} if devices else {}, host)


def run_data(tr, counters=None, window_ns=2000):
    return RunData("cell", {}, {}, {}, [], Window(0.0, 1.0, [], {}),
                   counters if counters is not None else {}, [], tr,
                   window_ns / 1e9)


def test_innermost_span_owns_each_moment():
    own = spans.owned([o for o in synthetic().host if o.name in spans.STAGES])
    assert own == {"spmoe.prefill": [(0, 300), (600, 1000)],
                   "spmoe.load_wave": [(300, 600), (1250, 1300)],
                   "spmoe.draft": [(1000, 1200)],
                   "spmoe.verify": [(1200, 1250), (1300, 1500)]}


def test_a_span_reaching_past_its_parent_is_cut_at_the_parent_end():
    own = spans.owned([Op("spmoe.verify", 0, 100),
                       Op("spmoe.load_wave", 50, 100)])
    assert own == {"spmoe.verify": [(0, 50)], "spmoe.load_wave": [(50, 100)]}


def test_idle_time_is_owned_time_less_device_busy_time():
    idle = spans.idle_s(synthetic())
    # prefill owns 700 ns, 100 of them busy; draft 200, 50 busy
    assert idle == {"spmoe.prefill": pytest.approx(600e-9),
                    "spmoe.load_wave": pytest.approx(350e-9),
                    "spmoe.draft": pytest.approx(150e-9),
                    "spmoe.verify": pytest.approx(250e-9)}


def test_idle_outside_every_serving_span_is_left_unattributed():
    tr = synthetic()
    rd = run_data(tr)
    idle_all = 100.0 * (1 - trace.busy_s(tr) / rd.trace_window_s)
    parts = [run.load_reader(n)(rd) for n in NEW[:4]]
    assert parts == [pytest.approx(100 * 350 / 2000),
                     pytest.approx(100 * 150 / 2000),
                     pytest.approx(100 * 250 / 2000),
                     pytest.approx(100 * 600 / 2000)]
    # [1500,2000) is idle under no serving span
    assert idle_all - sum(parts) == pytest.approx(100 * 500 / 2000)


def test_worker_and_other_host_events_are_never_a_stage():
    tr = synthetic()
    assert set(spans.idle_s(tr)) <= set(spans.STAGES)
    # the worker's events, alone, attribute nothing
    worker = Trace(tr.devices, [o for o in tr.host if o.name == WORKER])
    assert spans.idle_s(worker) is None


def test_none_without_a_device_plane_or_without_the_programs_spans():
    counters = {0: {"h2d_bytes": 10, "host_syncs": 14, "verify_syncs": 2,
                    "verify_blocks": 1}}
    bare = run_data(synthetic(devices=False), counters)
    for name in NEW[:4]:
        assert run.load_reader(name)(bare) is None, name
    # a program older than the spans and counters: a device plane, no
    # spmoe.* span, no verify_syncs counter
    old = run_data(Trace(synthetic().devices, [Op("python", 0, 2000)]),
                   {0: {"host_syncs": 2, "verify_blocks": 1}})
    for name in NEW:
        assert run.load_reader(name)(old) is None, name


def test_syncs_per_block_by_hand():
    counters = {0: {"h2d_bytes": 300, "host_syncs": 30, "verify_syncs": 4,
                    "verify_blocks": 2},
                1: {"h2d_bytes": 250, "host_syncs": 12, "verify_syncs": 2,
                    "verify_blocks": 1}}
    rd = run_data(synthetic(), counters)
    # 42 syncs over 3 blocks
    assert run.load_reader("host_syncs_per_block")(rd) == pytest.approx(14.0)


def test_spans_recorded_here_load_under_their_names(tmp_path):
    """Spans of the serving thread and of a second thread, as the profiler
    writes them, reach ``Trace.host`` under their plain names."""
    x = jnp.ones((8, 8))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def insert():
        with jax.profiler.TraceAnnotation("worker.insert"):
            jax.device_put(x).block_until_ready()

    with jax.profiler.TraceAnnotation("spmoe.verify", request_id="3"):
        with jax.profiler.TraceAnnotation("spmoe.load_wave", request_id="3"):
            jax.device_put(x).block_until_ready()
    t = threading.Thread(target=insert)
    t.start()
    t.join()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    names = [o.name for o in tr.host
             if o.name.startswith(("spmoe.", "worker."))]
    assert sorted(names) == ["spmoe.load_wave", "spmoe.verify",
                             "worker.insert"]
    own = spans.owned([o for o in tr.host if o.name in spans.STAGES])
    assert set(own) == {"spmoe.verify", "spmoe.load_wave"}
