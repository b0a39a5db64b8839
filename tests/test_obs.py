"""Observability pins: deterministic event payloads (bitwise-stable
across identical runs), exporter round-trips (JSONL, summary tree),
metrics snapshot/reset semantics, the retrace sentinel (positive AND
negative), the CI retrace gates for the three monitored entry points
(``serve.masked_step``, ``models.paged_decode``,
``launch.spmm_sharded``), the zero-cost contract when tracing is
disabled, the spans' mirror into the profiler's trace, the preparation
stage timers, the compile counters, and the ``smat.*`` device scopes of
the library op."""
import dataclasses
import glob
import json
import os
import re
import time

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import bcsr as bcsr_lib
from repro.kernels import autotune, ops
from repro.launch import dist_spmm
from repro.models import attention as A
from repro.models import transformer as T
from repro.obs import export, jaxmon, metrics, trace
from repro.serve.engine import Request, ServeEngine


def _sparse_cfg() -> ModelConfig:
    return ModelConfig(
        name="obs-test", family="dense", layout="attn_mlp",
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=97, dtype="float32",
        attn_sparsity=A.AttnSparsitySpec(mask=A.banded(32), block=(16, 16),
                                         backend="xla", interpret=True))


def _requests(n=3, max_new=3):
    rng = np.random.default_rng(0)
    lens = (3, 7, 5, 2, 6)
    return [Request(rid=i,
                    prompt=rng.integers(0, 97, size=lens[i % len(lens)],
                                        dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _instrumented_spmm_run():
    """One prepare+dispatch pass under a fresh autotuner, returning the
    captured events — the instrumented path the determinism pin replays."""
    autotune.set_autotuner(autotune.Autotuner())
    a = bcsr_lib.random_bcsr(0, (128, 64), (16, 16), 0.3)
    with trace.capture() as cap:
        arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32,
                                          reorder="jaccard")
        b = jnp.asarray(np.random.default_rng(1).standard_normal((64, 32)),
                        jnp.float32)
        ops.spmm(arrays, meta, b, backend="auto", interpret=True)
    return cap.events


# ------------------------------------------------------------ determinism
def test_deterministic_payloads_bitwise_stable_across_runs():
    """Two identical runs (fresh autotuner each) must produce IDENTICAL
    deterministic payloads — (kind, name, seq, span, parent, args) — and
    the same checksum.  Wall-clock fields are excluded by construction."""
    ev1 = _instrumented_spmm_run()
    ev2 = _instrumented_spmm_run()
    p1, p2 = (export.deterministic_events(e) for e in (ev1, ev2))
    assert p1, "instrumented path emitted no events"
    assert p1 == p2
    assert export.checksum(p1) == export.checksum(p2)
    names = {e.name for e in ev1}
    # the instrumented prepare pipeline + dispatch all show up
    assert {"prepare.reorder", "prepare.meta", "prepare.done",
            "autotune.pick", "ops.dispatch"} <= names


def test_span_nesting_and_args_are_jsonified():
    with trace.capture() as cap:
        with trace.span("outer", n=np.int64(3)):
            with trace.span("inner"):
                trace.event("leaf", xs=(1, 2), arr=np.arange(2))
    kinds = [(e.kind, e.name) for e in cap.events]
    assert kinds == [("B", "outer"), ("B", "inner"), ("I", "leaf"),
                     ("E", "inner"), ("E", "outer")]
    outer_b, inner_b, leaf = cap.events[:3]
    assert inner_b.parent == outer_b.span
    assert leaf.parent == inner_b.span      # instant events hang off the
    assert leaf.span is None                # enclosing span via parent
    # numpy scalars/arrays and tuples normalize to plain JSON types
    assert outer_b.args == {"n": 3}
    assert leaf.args == {"xs": [1, 2], "arr": [0, 1]}


# -------------------------------------------------------------- exporters
def test_jsonl_round_trip(tmp_path):
    path = os.path.join(tmp_path, "trace.jsonl")
    with trace.capture(path=path) as cap:
        with trace.span("work", k=1):
            trace.event("mark", v="x")
    read = export.read_jsonl(path)
    assert [e.to_dict() for e in read] == [e.to_dict() for e in cap.events]
    # and the sink wrote one JSON object per line
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == len(cap.events)


def test_summary_tree_renders_span_hierarchy():
    with trace.capture() as cap:
        for _ in range(2):
            with trace.span("phase"):
                with trace.span("sub"):
                    pass
                trace.event("tick")
    text = export.summary_tree(cap.events)
    assert "phase x2" in text
    assert "sub x2" in text
    assert "[event] tick x2" in text


# ---------------------------------------------------------------- metrics
def test_metrics_labels_snapshot_reset():
    r = metrics.Registry()
    r.counter("hits", op="spmm").inc()
    r.counter("hits", op="spmm").inc(2)
    r.counter("hits", op="sddmm").inc()
    r.gauge("level").set(0.25)
    h = r.histogram("lat")
    for v in (0.5, 3, 10_000):
        h.observe(v)
    snap = r.snapshot()
    assert snap["counters"] == {"hits{op=sddmm}": 1, "hits{op=spmm}": 3}
    assert snap["gauges"] == {"level": 0.25}
    hs = snap["histograms"]["lat"]
    assert hs["count"] == 3 and hs["min"] == 0.5 and hs["max"] == 10_000
    assert hs["buckets"]["le_1"] == 1 and hs["buckets"]["inf"] == 1
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_metrics_type_conflict_raises():
    r = metrics.Registry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_timeit_reduce_modes_and_validation():
    calls = []
    sec = metrics.timeit(lambda: calls.append(1), warmup=1, iters=3,
                         reduce="min")
    assert len(calls) == 4 and sec >= 0.0
    with pytest.raises(ValueError):
        metrics.timeit(lambda: None, reduce="mean")


# --------------------------------------------------------- retrace sentinel
def test_retrace_sentinel_counts_traces_not_calls():
    @jaxmon.monitor
    def poly(x):
        return x * 2

    f = jax.jit(poly)
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))                       # cache hit: no new trace
    assert jaxmon.trace_count(poly) == 1
    jaxmon.assert_max_traces(poly, 1)
    f(jnp.ones((3,)))                       # new shape -> retrace
    assert jaxmon.trace_count(poly) == 2
    with pytest.raises(jaxmon.RetraceError):
        jaxmon.assert_max_traces(poly, 1)
    poly(jnp.ones((4,)))                    # eager call: NOT a trace
    assert jaxmon.trace_count(poly) == 2
    jaxmon.reset(poly)
    assert jaxmon.trace_count(poly) == 0


def test_sentinel_registry_lookup_by_name():
    @jaxmon.monitor(name="obs_test.named")
    def g(x):
        return x + 1

    jax.jit(g)(jnp.zeros((2,)))
    assert jaxmon.trace_count("obs_test.named") == 1
    assert "obs_test.named" in jaxmon.sentinels()


# ----------------------------------------------------------- CI trace gates
def test_serve_engine_never_retraces():
    """The static-shape promise of the masked decode step: a full
    continuous-batching run with mixed prompt lengths, admissions and
    evictions traces ``serve.masked_step`` EXACTLY once."""
    cfg = _sparse_cfg()
    params = T.init_params(cfg, seed=0)
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=64)
    for _ in eng.generate([dataclasses.replace(r) for r in _requests()]):
        pass
    assert eng.step_sentinel.count == 1
    jaxmon.assert_max_traces(eng.step_sentinel, 1)


def test_paged_decode_traces_once_per_engine():
    """The paged KV decode body is scanned over layers — one trace per
    engine program, regardless of layer count or tokens decoded."""
    cfg = _sparse_cfg()
    params = T.init_params(cfg, seed=0)
    jaxmon.reset("models.paged_decode")
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=64)
    assert eng.paged_kv is not None        # the paged path is actually on
    for _ in eng.generate([dataclasses.replace(r) for r in _requests()]):
        pass
    assert jaxmon.trace_count("models.paged_decode") == 1
    jaxmon.assert_max_traces("models.paged_decode", 1)


def test_spmm_sharded_traces_once_under_jit():
    a = bcsr_lib.random_bcsr(0, (128, 64), (16, 16), 0.3)
    sharr, smeta = dist_spmm.prepare_sharded(a, 2, dtype=jnp.float32)
    b = jnp.asarray(np.random.default_rng(0).standard_normal((64, 32)),
                    jnp.float32)
    jaxmon.reset("launch.spmm_sharded")
    fn = jax.jit(lambda bb: dist_spmm.spmm_sharded(sharr, smeta, bb,
                                                   backend="xla",
                                                   n_chunks=2))
    ref = np.asarray(fn(b))
    np.testing.assert_allclose(np.asarray(fn(b)), ref)
    assert jaxmon.trace_count("launch.spmm_sharded") == 1
    jaxmon.assert_max_traces("launch.spmm_sharded", 1)


# ------------------------------------------------------- disabled => free
def test_disabled_tracing_is_zero_cost():
    """With REPRO_TRACE off: no state, a shared null span (no per-call
    allocation), event() returns None, and nothing is buffered."""
    assert trace._state is None or trace.enabled()  # env-dependent guard
    trace.configure(None)
    try:
        assert not trace.enabled()
        s1 = trace.span("x", a=1)
        s2 = trace.span("y")
        assert s1 is s2 is trace._NULL_SPAN
        with s1:
            pass
        assert trace.event("z", k=2) is None
        assert trace.timed_event("w", 1.0) is None
        assert trace.get_events() == []
        assert metrics.timeit(lambda: None, warmup=0, iters=1) >= 0.0
    finally:
        trace.configure(os.environ.get("REPRO_TRACE"))


def test_capture_works_even_when_disabled():
    trace.configure(None)
    try:
        with trace.capture() as cap:
            with trace.span("s"):
                trace.event("e")
        assert [e.name for e in cap.events] == ["s", "e", "s"]
        assert not trace.enabled()          # restored to disabled
    finally:
        trace.configure(os.environ.get("REPRO_TRACE"))


# ------------------------------------------------- profiler mirror, stages
def _prepare_matrix():
    """A host CSR whose preparation takes tens of milliseconds, so the
    stage timers dominate the few calls between them."""
    rng = np.random.default_rng(3)
    dense = (rng.random((512, 512)) < 0.02) * rng.standard_normal((512, 512))
    return sp.csr_matrix(dense.astype(np.float32))


def _host_span_names(tmp_path, fn) -> set:
    """Names of the host plane's events in a CPU profiler trace of
    ``fn()``."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(tmp_path, "plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


def _prepare(csr):
    a = bcsr_lib.from_scipy(csr, (16, 16))
    return ops.prepare_sparse(a, dtype=jnp.float32, reorder="jaccard")


def test_spans_reach_the_profiler_trace_while_tracing(tmp_path):
    csr = _prepare_matrix()

    def traced():
        with trace.capture():
            _prepare(csr)

    names = _host_span_names(tmp_path, traced)
    assert {"prepare.blocking", "prepare.reorder", "prepare.reorder.cluster",
            "prepare.meta", "prepare.to_device"} <= names


def test_no_spans_in_the_profiler_trace_when_disabled(tmp_path):
    csr = _prepare_matrix()
    trace.configure(None)
    try:
        names = _host_span_names(tmp_path, lambda: _prepare(csr))
    finally:
        trace.configure(os.environ.get("REPRO_TRACE"))
    assert not any(n.startswith("prepare.") for n in names)


def test_prepare_stage_gauges_cover_the_preparation():
    csr = _prepare_matrix()
    _prepare(csr)                                # first-call costs
    metrics.reset()
    t0 = time.perf_counter()
    _prepare(csr)
    wall = time.perf_counter() - t0
    gauges = metrics.snapshot()["gauges"]
    stages = [gauges[f"prepare.seconds{{stage={s}}}"]
              for s in ("blocking", "reorder", "meta", "to_device")]
    assert all(v > 0 for v in stages)
    assert sum(stages) == pytest.approx(wall, rel=0.05)
    assert "prepare.nnzb" not in "".join(gauges)


def test_compile_listener_counts_backend_compiles():
    x4, x5 = jnp.ones((4,)), jnp.ones((5,))
    f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)

    def compiles():
        return metrics.snapshot()["counters"].get("jax.compiles", 0)

    before = compiles()
    f(x4).block_until_ready()                    # new shape: one compile
    assert compiles() == before + 1
    f(x4).block_until_ready()                    # cache hit: none
    assert compiles() == before + 1
    f(x5).block_until_ready()
    assert compiles() == before + 2
    secs = metrics.snapshot()["gauges"]
    assert secs["jax.compile.seconds{phase=compile}"] > 0
    assert secs["jax.compile.seconds{phase=trace}"] > 0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_spmm_hlo_names_its_steps(backend):
    """The op's ``smat.*`` scopes: the padding of B, the kernel under the
    backend it runs, and the epilogue (crop and un-permute after the
    reorder).  All three reach the module handed to the compiler; the
    compiled HLO's ``op_name`` metadata keeps each step that stays an
    operation of its own (XLA:CPU folds the ``xla`` backend's pad into
    the kernel's gather, which then carries the kernel's scope)."""
    a = bcsr_lib.random_bcsr(0, (128, 64), (16, 16), 0.3)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32,
                                      reorder="jaccard")
    b = jnp.ones((64, 32), jnp.float32)          # N = 32: padded to 128
    fn = jax.jit(lambda ar, bb: ops.spmm(ar, meta, bb, backend=backend,
                                         interpret=True))
    lowered = fn.lower(arrays, b)
    steps = {"smat.pad", f"smat.kernel.{backend}", "smat.epilogue"}
    assert steps <= set(re.findall(r"smat\.[a-z_.]+[a-z]",
                                   lowered.as_text(debug_info=True)))
    compiled = {p for name in re.findall(r'op_name="([^"]*)"',
                                         lowered.compile().as_text())
                for p in name.split("/") if p.startswith("smat.")}
    kept = steps if backend == "pallas" else steps - {"smat.pad"}
    assert kept <= compiled
