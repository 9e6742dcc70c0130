"""The trace reduction, on hand-built events and on a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as tr

E = tr.Event


def _reduction():
    # device ops over a 0..100 ns window: busy [10,30) U [25,40) U [60,70)
    ops = [E("fusion.featurize", 10, 30), E("sort.1", 25, 40),
           E("matvec", 60, 70), E("outside", 120, 130)]
    host = [E("chipbench.window", 0, 100), E("chipbench.fit", 5, 95),
            E("PjitFunction(fit)", 42, 58), E("fit.pcg_solve", 72, 99)]
    return tr.Reduction([ops], host, 0, 100)


def test_union_merges_overlaps_and_clips_to_the_window():
    evs = [E("a", -5, 10), E("b", 8, 20), E("c", 30, 40), E("d", 95, 140)]
    assert tr.union(evs, 0, 100) == [(0, 20), (30, 40), (95, 100)]


def test_busy_idle_and_op_time():
    red = _reduction()
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)           # 30 + 10
    assert red.idle_share() == pytest.approx(0.6)
    assert red.op_seconds("featurize") == pytest.approx(20e-9)
    assert red.op_seconds("sort") == pytest.approx(15e-9)
    assert red.op_seconds("nothing") == 0.0
    assert red.top_ops(2) == [["fusion.featurize", pytest.approx(20e-9)],
                              ["sort.1", pytest.approx(15e-9)]]


def test_idle_gaps_go_to_the_innermost_host_event():
    red = _reduction()
    assert red.gaps() == [(0, 10), (40, 60), (70, 100)]
    gaps = dict((k, v) for k, v in red.idle_by_host())
    # (0,10): mid 5 -> chipbench.fit starts at 5; (40,60): mid 50 ->
    # PjitFunction; (70,100): mid 85 -> fit.pcg_solve
    assert gaps == {"chipbench.fit": pytest.approx(10e-9),
                    "PjitFunction(fit)": pytest.approx(20e-9),
                    "fit.pcg_solve": pytest.approx(30e-9)}
    assert set(red.breakdown()) == {"device_ops", "idle_gaps"}


def test_gap_with_no_host_event_is_named_so():
    red = tr.Reduction([[E("op", 0, 10)]], [], 0, 20)
    assert red.idle_by_host() == [[tr.NO_HOST_EVENT, pytest.approx(10e-9)]]


# two chips' planes and a host plane, as the profiler writes them on a TPU
# host: op events on "XLA Ops" with JAX's name stack in a tf_op stat, the
# jitted programs on "XLA Modules"; times in ps from each line's start
TPU_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000 duration_ps: 20000
             stats { metadata_id: 9 str_value: "jit(fit)/jit(featurize_pallas)/pallas_call" } }
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 70000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 60000 }
    events { metadata_id: 5 offset_ps: 65000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.3" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.7" } }
  event_metadata { key: 4 value { id: 4 name: "jit_fit(1)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_pcg_step(2)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.2" } } }
planes { id: 3 name: "/device:TPU_NON_CORE:0" }
planes { id: 4 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.fit" } } }
"""


def test_tpu_planes_give_scope_and_per_device_means():
    from jax.profiler import ProfileData
    devices, host = tr.events_of(ProfileData.from_text_proto(TPU_XSPACE))
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    ops = devices["/device:TPU:0"]
    assert [(e.name, e.start, e.end) for e in ops] == [
        ("custom-call.1", 1010, 1030), ("sort.3", 1040, 1050),
        ("fusion.7", 1070, 1080)]
    assert ops[0].scope == \
        "jit(fit)/jit(featurize_pallas)/pallas_call jit_fit(1)"
    assert ops[2].scope == "jit_pcg_step(2)"
    span = tr.host_spans(host, "chipbench.window")[0]
    red = tr.Reduction([devices[k] for k in sorted(devices)], host,
                       span.start, span.end)
    # chip 0 busy 40 ns, chip 1 busy all 100 ns of the window
    assert red.busy_s == pytest.approx(70e-9)
    assert red.op_seconds("featurize") == pytest.approx(10e-9)  # 20 / 2
    assert red.op_seconds("pcg_step") == pytest.approx(5e-9)
    # chip 0's gaps: (1000,1010), (1030,1040) and (1080,1100) under the
    # window alone, (1050,1070) under chipbench.fit
    assert dict(map(tuple, red.idle_by_host())) == {
        "chipbench.window": pytest.approx(40e-9),
        "chipbench.fit": pytest.approx(20e-9)}


def test_recorded_trace_finds_host_spans_and_refuses_without_a_chip(
        tmp_path):
    f = jax.jit(lambda x: jnp.sort(x) * 2.0)
    x = jnp.arange(4096.0)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = tr.load(tr.xplane_file(str(tmp_path)))
    spans = tr.host_spans(host, "chipbench.window")
    assert len(spans) == 1 and spans[0].end > spans[0].start
    assert devices == {}                 # the CPU has no TPU plane
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce_dir(str(tmp_path), "chipbench.window")


def test_fit_readers_on_a_hand_built_run():
    from types import SimpleNamespace

    from chipbench import harness, roofline
    from chipbench.run import RunInfo
    # two fits of 20 and 30 PCG iterations, 1 s and 2 s on the host clock
    info = {"fits": 2, "pcg_iters": [20, 30], "n": 1000, "m": 4, "d": 8,
            "k": 1}
    run = RunInfo(None, None, SimpleNamespace(seconds=[1.0, 2.0]),
                  _reduction(), info, "TPU v5 lite")
    read = {name: harness.reader(name)(run) for name in (
        "featurize_ms", "index_sort_ms", "pcg_iter_ms", "pcg_iters",
        "matvec_roofline", "fit_mfu", "device_idle_pct.fit")}
    assert read["featurize_ms"] == pytest.approx(20e-9 / 2 * 1e3)
    assert read["index_sort_ms"] == pytest.approx(15e-9 / 2 * 1e3)
    # busy 40 ns less featurize 20 and sort 15, over 50 iterations
    assert read["pcg_iter_ms"] == pytest.approx(5e-9 / 50 * 1e3)
    assert read["pcg_iters"] == 25
    assert read["device_idle_pct.fit"] == pytest.approx(60.0)
    pk = roofline.peaks("TPU v5 lite")
    flops = 2 * 8 * 4 * 1000 * 8 + 50 * 4 * 4 * 1000
    assert read["fit_mfu"] == pytest.approx(
        100 * flops / (3.0 * pk["bf16_flops_per_s"]))
    assert 0 < read["fit_mfu"] < 100
    least = roofline.least_seconds(*roofline.matvec_work(1000, 4, 1), pk)
    assert read["matvec_roofline"] == pytest.approx(
        100 * least / (read["pcg_iter_ms"] * 1e-3))
