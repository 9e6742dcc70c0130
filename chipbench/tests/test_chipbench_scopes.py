"""The readers of the program's named parts: the PCG loop, the matvec's
kernel, the fit outside them, and the batcher's wait, fill and host path.
On hand-built traces, on the program's lowering, and on a profiler capture
made here."""
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, trace as tr
from chipbench.run import RunInfo

E = tr.Event
FIT_READERS = ("pcg_loop_ms", "matvec_kernel_ms", "fit_outside_loop_ms")


def _op(instr, start, end, scope=""):
    """A device op named as on a TPU trace: its HLO instruction."""
    return E(f"%{instr} = f32[8]{{0}} op()", start, end, scope)


def _fit_ops(kernel="wlsh_fused_matvec"):
    """One device over a 0..1000 ns window: featurize, the index build's
    sort and ``while``, an eager matvec, then the PCG ``while`` of two
    iterations enclosing its body, then the eager table loads."""
    ops = [_op("wlsh_featurize.1", 0, 20), _op("sort.1", 20, 60),
           _op("while.1", 60, 70), _op(f"{kernel}.7", 80, 100),
           _op("while.5", 100, 600)]
    for t in (100, 350):                 # per iteration: 250 ns
        ops += [_op("fusion.31", t + 10, t + 110),
                _op(f"{kernel}.63", t + 110, t + 150),
                _op(f"{kernel}.69", t + 150, t + 170),
                _op("fusion.36", t + 170, t + 220),
                _op("fusion.40", t + 220, t + 240),
                _op("fusion.2", t + 240, t + 245)]
    return ops + [_op("fusion.50", 650, 700)]


def _fit_run(ops, fits=2, iters=(1, 1)):
    red = tr.Reduction([ops], [E("chipbench.window", 0, 1000)], 0, 1000)
    info = {"fits": fits, "pcg_iters": list(iters), "n": 1000, "m": 4,
            "d": 8, "k": 1}
    return RunInfo(None, None, SimpleNamespace(seconds=[1.0] * fits), red,
                   info, "TPU v5 lite")


def test_fit_readers_take_unions_over_their_bases():
    run = _fit_run(_fit_ops())
    read = {n: harness.reader(n)(run) for n in FIT_READERS + (
        "featurize_ms", "index_sort_ms", "pcg_iter_ms")}
    ms = 1e-6                            # 1 ns in ms
    # the while holds its body: the union is its 500 ns, where a sum of
    # the loop's op durations would count 500 + 2 * 235
    assert read["pcg_loop_ms"] == pytest.approx(500 / 2 * ms)
    # the loop's two kernel calls an iteration; the eager call is outside
    assert read["matvec_kernel_ms"] == pytest.approx(60 * ms)
    # busy 20 + 40 + 10 + 20 + 500 + 50 = 640 ns; outside: the index
    # build's while, the eager matvec and the loads, 80 ns over two fits
    assert read["fit_outside_loop_ms"] == pytest.approx(40 * ms)
    busy_per_fit = run.trace.busy_s / 2 * 1e3
    assert read["featurize_ms"] + read["index_sort_ms"] \
        + read["fit_outside_loop_ms"] + read["pcg_loop_ms"] * 1 \
        == pytest.approx(busy_per_fit)
    assert read["matvec_kernel_ms"] <= read["pcg_loop_ms"]
    # the accepted readers read as before: busy less featurize and sort
    assert read["pcg_iter_ms"] == pytest.approx(580 / 2 * ms)


@pytest.mark.parametrize("kernel", ["wlsh_blocked_gather",
                                    "wlsh_readout_gather"])
def test_any_table_kernel_marks_the_loop(kernel):
    run = _fit_run(_fit_ops(kernel))
    assert harness.reader("pcg_loop_ms")(run) == pytest.approx(250e-6)
    assert harness.reader("matvec_kernel_ms")(run) == pytest.approx(60e-6)


def test_a_program_without_the_names_reports_none():
    """The parent's kernels (``bin_fused_matvec_pallas``) mark no loop."""
    run = _fit_run(_fit_ops("bin_fused_matvec_pallas"))
    for name in FIT_READERS:
        assert harness.reader(name)(run) is None, name
    assert harness.reader("pcg_iter_ms")(run) is not None


def _serve_run(host, ops):
    red = tr.Reduction([ops], host, 1000, 2000)
    return RunInfo(None, None, None, red, {"batches": 2}, "TPU v5 lite")


def test_serve_readers_per_batch():
    host = [E("chipbench.window", 1000, 2000),
            E("serve.batch_fill", 900, 990),        # before the window
            E("serve.batch_predict", 990, 1100),
            E("serve.batch_fill", 1100, 1120),
            E("serve.batch_predict", 1120, 1400),
            E("serve.batch_fill", 1400, 1460),
            E("serve.batch_predict", 1460, 1700),
            E("serve.await_request", 1700, 2000)]
    # device work inside each batch, nested ops counted once
    ops = [E("jit_fn", 1150, 1350), E("wlsh_readout_gather.1", 1160, 1340),
           E("jit_fn", 1500, 1600)]
    run = _serve_run(host, ops)
    ms = 1e-6
    assert harness.reader("batch_fill_ms")(run) == pytest.approx(40 * ms)
    # (280 - 200) and (240 - 100)
    assert harness.reader("batch_host_ms")(run) == pytest.approx(110 * ms)
    assert harness.reader("readout_ms")(run) == pytest.approx(150 * ms)


def test_serve_readers_without_the_program_events():
    run = _serve_run([E("chipbench.window", 1000, 2000)],
                     [E("jit_fn", 1150, 1350)])
    assert harness.reader("batch_fill_ms")(run) is None
    assert harness.reader("batch_host_ms")(run) is None


def test_queue_wait_reads_the_batchers_histogram(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "REGISTRY", obs.MetricsRegistry())
    read = harness.reader("queue_wait_ms")
    assert read(None) is None
    obs.REGISTRY.histogram("serve_queue_wait_us").labels().observe_many(
        [1000.0, 3000.0, 5000.0])
    assert read(None) == pytest.approx(3.0)


def test_the_loop_and_its_matvec_parts_are_named_in_the_program():
    """The lowered PCG solve over the fused matvec: every op of the loop's
    cond and body is in ``wlsh.pcg``, the matvec's parts in their scopes,
    and no op holds the ``featurize`` or ``sort`` that other readers
    match."""
    from repro.core import get_bucket_fn, make_operator, sample_lsh_params
    from repro.core.krr import pcg_solve
    from repro.core.lsh import GammaPDF
    n, d, m = 256, 3, 2
    lsh = sample_lsh_params(jax.random.PRNGKey(0), m, d, GammaPDF(2.0, 1.0))
    op = make_operator(lsh, get_bucket_fn("rect"), 512, backend="pallas",
                       platform="cpu")
    assert op.interpret
    x = jax.random.uniform(jax.random.PRNGKey(1), (n, d))
    idx = op.build_index(op.featurize(x), blocked=True)

    def solve(y):
        return pcg_solve(lambda v: op.matvec(idx, v), y, 1.0, maxiter=3).x

    text = jax.jit(solve).lower(jnp.ones(n)).as_text(dialect="hlo",
                                                     debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    loop = [s for s in names if s.startswith("jit(solve)/while/")]
    assert loop and all(
        re.match(r"jit\(solve\)/while/(body|cond)/wlsh\.pcg/", s)
        for s in loop)
    assert any("wlsh.pcg/wlsh.matvec.layout/gather" in s for s in loop)
    assert any("wlsh.pcg/wlsh.matvec.kernel/" in s for s in loop)
    assert "wlsh_fused_matvec" in text
    assert not [s for s in names if "featurize" in s or "sort" in s]


def test_a_capture_shows_the_batchers_wait_and_fill(tmp_path):
    from repro import obs
    from repro.serve import MicroBatcher
    trace_dir = str(tmp_path)
    obs.start_trace(trace_dir)
    try:
        with MicroBatcher(lambda xb: xb.sum(axis=1), max_batch=4,
                          max_wait_us=2000, dim=3) as mb:
            time.sleep(0.05)
            futs = [mb.submit(np.ones(3, np.float32)) for _ in range(6)]
            assert [f.result(5.0) for f in futs] == [3.0] * 6
    finally:
        obs.stop_trace()
    _, host = tr.load(tr.xplane_file(trace_dir))
    names = {e.name for e in host}
    assert {"serve.await_request", "serve.batch_fill",
            "serve.batch_predict"} <= names
    waits = tr.host_spans(host, "serve.await_request")
    assert max(e.end - e.start for e in waits) >= 40e6     # the 50 ms idle


def test_set_up_records_no_queue_wait():
    """The serving set-up warms and fills the cache through ``Predictor``
    directly, so the batcher's histogram holds the window's requests
    only."""
    from repro import obs
    from chipbench import open_loop
    cell = harness.Cell("forest.serve_zipf")
    cell.config.update(n_train=4096, n_test=64, m=4, table_size=1 << 14,
                       backend="reference")
    cell.traffic.update(rate_per_s=200.0, pool=2048, cache_prefill=256)
    hist = obs.histogram("serve_queue_wait_us").labels()
    before = hist.count
    st = open_loop.setup(cell, 2**35 + 5, 0.2)
    assert hist.count == before
    win = open_loop.measure(st, 0.2)
    assert hist.count - before == win.attempted
