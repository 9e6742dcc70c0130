"""Serving cells: open-loop requests through ``MicroBatcher`` ->
``Predictor.predict``.

Set-up makes the served model from the seed (LSH instances drawn at the
configuration's settings over its training points, and the tables of
``weights.make_tables``), hosts it in a ``Predictor`` with the bucket-exact
cache, compiles the padding buckets the batcher can produce, and builds
the mix's schedule and rows.  A Zipf mix also fills the cache with its
most popular rows through ``Predictor.predict`` in bulk, as a server that
has been up for a while would have it.

The window submits each row at its due time whatever the state of earlier
requests, and times each from its due time to its answer.  Every answer
is then checked against the plain reference readout of the same tables.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import data, harness, reference, traffic, weights

COUNTERS = ("serve_cache_hits_total", "serve_cache_misses_total",
            "serve_padding_bucket_total")


def padding_buckets(limit: int) -> tuple[int, ...]:
    return tuple(1 << p for p in range((limit - 1).bit_length() + 1))


class ProgramServe:
    """The system under test: one model hosted by ``repro.serve.Predictor``
    with its bucket-exact cache."""

    def __init__(self, cfg: dict, lsh, tables):
        import jax.numpy as jnp
        from repro.core.krr import WLSHKRRModel, model_operator
        from repro.core.lsh import LSHParams
        from repro.serve import Predictor
        from repro.serve.artifact import LoadedArtifact
        s = cfg["serve"]
        model = WLSHKRRModel(
            lsh=LSHParams(*lsh), bucket_name=cfg["bucket"],
            beta=jnp.zeros((0,), jnp.float32), tables=tables,
            table_size=cfg["table_size"], cg_iters=jnp.asarray(0),
            cg_resnorm=jnp.asarray(0.0), backend=cfg["backend"])
        self.predictor = Predictor(cache_entries=s["cache_entries"],
                                   max_batch=s["predictor_max_batch"])
        self.predictor.add_model(LoadedArtifact(
            artifact_id=cfg["name"], model=model,
            operator=model_operator(model), norm=None, meta={}))

    def warm(self, sizes) -> None:
        self.predictor.warmup(sizes=tuple(sizes))

    def predict(self, x):
        return self.predictor.predict(x)


def make_traffic(cfg: dict, mix: dict, seed: int, seconds: float,
                 rate: float):
    """(rows, due times, cache prefill rows) of the mix for ``seed``."""
    rng = harness.seed_rng(seed, 4)
    due = traffic.poisson_due_times(rate, seconds, rng)
    if mix["rows"] == "fresh":
        return traffic.uniform_rows(len(due), cfg["d"], rng), due, \
            np.zeros((0, cfg["d"]), np.float32)
    if mix["rows"] == "zipf":
        pool = traffic.uniform_rows(mix["pool"], cfg["d"], rng)
        ranks = traffic.zipf_ranks(len(due), mix["pool"], mix["zipf_s"], rng)
        return pool[ranks], due, pool[:mix["cache_prefill"]]
    raise ValueError(f"unknown rows {mix['rows']!r}")


class State(NamedTuple):
    cfg: dict
    traffic: dict
    lsh: tuple
    tables: object          # (m, B) device array, the benchmark's own
    system: object
    rows: np.ndarray
    due: np.ndarray


def setup(cell, seed: int, seconds: float, system=None) -> State:
    """``system`` builds the server from (cfg, lsh, tables); the program's
    ``ProgramServe`` unless a control or a test stands in."""
    import jax
    cfg, mix = cell.config, cell.traffic
    x = data.train_points(cfg, harness.seed_key(seed, 1))
    ls = data.lengthscale(x)
    lsh = jax.block_until_ready(reference.sample_lsh(
        harness.seed_key(seed, 2), cfg["m"], cfg["d"], *cfg["gamma_pdf"],
        ls))
    tables = jax.block_until_ready(weights.make_tables(
        lsh, x, harness.seed_key(seed, 3), table_size=cfg["table_size"]))
    del x
    system = (system or ProgramServe)(cfg, lsh, tables)
    s = cfg["serve"]
    rows, due, prefill = make_traffic(cfg, mix, seed, seconds,
                                      mix["rate_per_s"])
    sizes = set(padding_buckets(s["batcher_max_batch"]))
    if len(prefill):
        sizes.add(min(s["predictor_max_batch"],
                      1 << (len(prefill) - 1).bit_length()))
    system.warm(sorted(sizes))
    if len(prefill):
        system.predict(prefill)
    return State(cfg, mix, lsh, tables, system, rows, due)


class Window(NamedTuple):
    outcome: traffic.Outcome
    counters: dict
    window_s: float
    attempted: int
    failed: int
    e2e: dict


def measure(st: State, seconds: float, tracing: bool = False) -> Window:
    from repro.serve import MicroBatcher
    s = st.cfg["serve"]
    note = harness.annotation if tracing else harness.no_annotation

    def predict_fn(xb):
        with note("chipbench.batch"):
            return st.system.predict(xb)

    before = harness.counters(COUNTERS)
    t0 = harness.now()
    with MicroBatcher(predict_fn, max_batch=s["batcher_max_batch"],
                      max_wait_us=s["batcher_max_wait_us"],
                      dim=st.cfg["d"]) as mb:
        with note("chipbench.window"):
            out = traffic.send_open_loop(mb.submit, st.rows, st.due,
                                         annotate=note if tracing else None)
    window_s = harness.now() - t0
    after = harness.counters(COUNTERS)
    lat_ms = out.latencies() * 1e3
    e2e = {"serve_p50_ms": traffic.percentile(lat_ms, 50),
           "serve_p95_ms": traffic.percentile(lat_ms, 95)}
    return Window(out, {k: after[k] - before[k] for k in COUNTERS},
                  window_s, len(st.due), out.failed(), e2e)


def release(st: State, win: Window) -> Window:
    """Drop the program's server (its hosted model and compiled programs)
    before the reference runs."""
    st.system.__dict__.clear()
    return win


def check(st: State, win: Window) -> dict:
    """Widest gap between a served answer and the reference readout, over
    every answered request, relative to the RMS of the reference answers;
    and the count of requests never answered."""
    out = win.outcome
    answered = [i for i, v in enumerate(out.value) if v is not None]
    served = np.array([float(out.value[i]) for i in answered])
    hashes = reference.hash_points(st.lsh, st.rows[answered],
                                   st.cfg["table_size"])
    gap, ref = reference.answer_gaps(hashes, st.tables, served)
    rel = float(gap.max() / np.sqrt(np.mean(ref ** 2))) if len(gap) else \
        float("inf")
    limits = st.cfg["limits"]
    return {"answer_gap": (rel, limits["answer_gap"]),
            "unanswered": (float(len(st.due) - len(answered)), 0.0)}


def layer_info(st: State, win: Window) -> dict:
    c = win.counters
    return {"hits": c["serve_cache_hits_total"],
            "misses": c["serve_cache_misses_total"],
            "batches": c["serve_padding_bucket_total"],
            "lateness_p95_ms": traffic.percentile(
                win.outcome.lateness() * 1e3, 95)}
