"""Autotuned dispatch tests: registry, fingerprints, analytic + measured
picks, JSON cache persistence, and the ``backend="auto"`` wiring through
``ops.spmm`` and ``SparsitySpec``."""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcsr as bcsr_lib
from repro.core.sparse_linear import (SparsitySpec, apply_sparse_linear,
                                      init_sparse_linear)
from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _fresh_tuner():
    """Isolate the process-wide tuner per test."""
    autotune.set_autotuner(autotune.Autotuner())
    yield
    autotune.set_autotuner(None)


def _mk(seed=0, shape=(96, 128), block=(16, 16), density=0.3):
    return bcsr_lib.random_bcsr(seed, shape, block,
                                density).ensure_nonempty_rows()


# ------------------------------------------------------------------ registry
def test_registry_has_all_variants():
    names = autotune.variant_names()
    assert names == ("nnz_stream", "xla", "dense")
    for n in names:
        v = autotune.get_variant(n)
        assert v.backend in ops.BACKENDS
        assert v.bn_candidates


def test_register_duplicate_rejected():
    v = autotune.get_variant("xla")
    with pytest.raises(ValueError):
        autotune.register_variant(v)


# --------------------------------------------------------------- fingerprint
def test_fingerprint_meta_matches_bcsr():
    a = _mk()
    _, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    assert (autotune.fingerprint(meta, 64).key()
            == autotune.fingerprint_bcsr(a, 64).key())


def test_fingerprint_buckets_n():
    a = _mk()
    _, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    assert (autotune.fingerprint(meta, 65).key()
            == autotune.fingerprint(meta, 128).key())
    assert (autotune.fingerprint(meta, 64).key()
            != autotune.fingerprint(meta, 128).key())


# ------------------------------------------------------------ analytic picks
def test_analytic_choice_is_registered_and_supported():
    a = _mk()
    _, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    c = autotune.analytic_choice(meta, 256)
    v = autotune.get_variant(c.variant)
    assert v.supported(meta)
    assert c.bn in v.bn_candidates
    assert c.source == "analytic"


def test_analytic_choice_skips_row_loop_without_max_bpr():
    # hand-built meta (specs path): max_bpr unknown, so the fused
    # attention, whose static schedule max_bpr sizes, is never proposed
    meta = ops.SparseMeta(shape=(128, 128), block=(16, 16), n_block_rows=8,
                          n_block_cols=8, nnzb=16, nnzb_t=16)
    assert not autotune.get_variant("attn_fused").supported(meta)
    c = autotune.analytic_choice(meta, 128, op="attn")
    assert c.variant == "attn_composed"


def test_pick_caches_in_memory():
    a = _mk()
    _, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    t = autotune.get_autotuner()
    c1 = t.pick(meta, 64)
    assert len(t) == 1
    assert t.pick(meta, 64) is c1


# ----------------------------------------------------------- measured sweeps
def test_tune_never_slower_than_default_and_persists(tmp_path):
    cache = tmp_path / "autotune.json"
    tuner = autotune.Autotuner(cache_path=str(cache))
    a = _mk(seed=2, shape=(128, 128), density=0.2)
    choice, timings = tuner.tune(a, 64, iters=2, interpret=True)
    assert choice.source == "measured"
    default_label = f"{autotune.DEFAULT_VARIANT}/bn{autotune.DEFAULT_BN}"
    tuned_label = f"{choice.variant}/bn{choice.bn}"
    assert default_label in timings
    # acceptance gate: the cached pick is never slower than the hardcoded
    # default (2% tie-break band)
    assert timings[tuned_label] <= timings[default_label] * 1.02

    # persisted and reloaded by a fresh tuner
    payload = json.loads(cache.read_text())
    assert payload["version"] == 1 and payload["entries"]
    tuner2 = autotune.Autotuner(cache_path=str(cache))
    fp = autotune.fingerprint_bcsr(a, 64)
    hit = tuner2.get(fp)
    assert hit is not None
    assert (hit.variant, hit.bn, hit.source) == (choice.variant, choice.bn,
                                                 "measured")


def _failing_backend(monkeypatch, backend):
    """Make every ``ops.spmm`` dispatch on ``backend`` raise."""
    real = ops.spmm

    def spmm(*a, **kw):
        if kw.get("backend") == backend:
            raise NotImplementedError(f"{backend} unavailable")
        return real(*a, **kw)
    monkeypatch.setattr(ops, "spmm", spmm)


def test_tune_records_failed_candidates(monkeypatch):
    from repro.obs import trace
    _failing_backend(monkeypatch, "dense")
    tuner = autotune.Autotuner()
    a = _mk(seed=2, shape=(128, 128), density=0.2)
    with trace.capture() as cap:
        choice, timings = tuner.tune(a, 64, iters=1, interpret=True)
    failed = {k for k, v in timings.items() if isinstance(v, str)}
    assert failed and all(k.startswith("dense/") for k in failed)
    assert all(timings[k] == "failed: NotImplementedError" for k in failed)
    assert choice.source == "measured" and choice.variant != "dense"
    events = {e.args["candidate"] for e in cap.events
              if e.name == "autotune.candidate_failed"}
    assert events == failed


@pytest.mark.parametrize("failing", ["pallas", "compiled-on-cpu"])
def test_tune_raises_when_default_fails(monkeypatch, failing):
    """A sweep whose default candidate cannot run caches nothing: neither
    when the kernel raises, nor when non-interpret Pallas is asked of a
    CPU backend."""
    interpret = True
    if failing == "pallas":
        _failing_backend(monkeypatch, "pallas")
    elif jax.default_backend() == "cpu":
        interpret = False
    else:
        pytest.skip("compiled Pallas runs on this backend")
    tuner = autotune.Autotuner()
    a = _mk(seed=2, shape=(128, 128), density=0.2)
    with pytest.raises(RuntimeError, match="default candidate"):
        tuner.tune(a, 64, iters=1, interpret=interpret)
    assert len(tuner) == 0


def test_corrupt_cache_tolerated(tmp_path):
    cache = tmp_path / "bad.json"
    cache.write_text("{not json")
    tuner = autotune.Autotuner(cache_path=str(cache))
    assert len(tuner) == 0


# ---------------------------------------------------------------- ops wiring
def test_spmm_auto_matches_oracle():
    a = _mk(seed=3)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    b = jnp.asarray(np.random.default_rng(5).standard_normal(
        (a.shape[1], 64)).astype(np.float32))
    want = ops.spmm(arrays, meta, b, backend="xla")
    got = ops.spmm(arrays, meta, b, backend="auto", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_spmm_auto_uses_measured_cache_entry():
    a = _mk(seed=4)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    n = 64
    choice, _ = autotune.get_autotuner().tune(a, n, iters=1,
                                               interpret=True)
    backend, bn = ops.resolve_backend("auto", 512, meta, n)
    assert backend == autotune.get_variant(choice.variant).backend
    assert bn == choice.bn


def test_backend_alias_and_unknown():
    a = _mk(seed=8)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    assert ops.resolve_backend("nnz_stream", 256, meta, 64) == ("pallas", 256)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.resolve_backend("cuda", 256, meta, 64)


def test_explicit_row_loop_without_max_bpr_raises():
    meta = ops.SparseMeta(shape=(128, 128), block=(16, 16), n_block_rows=8,
                          n_block_cols=8, nnzb=16, nnzb_t=16)
    # `row_loop` is no backend: an explicit request fails loudly, never a
    # silent switch to a kernel the caller did not ask for
    with pytest.raises(ValueError, match="unknown backend"):
        ops.resolve_backend("row_loop", 512, meta, 128)
    # auto resolves inside the backend list, for both ops
    for op in ("spmm", "sddmm"):
        assert ops.resolve_backend("auto", 512, meta, 128,
                                   op=op)[0] in ops.BACKENDS


# -------------------------------------------------------- SparsitySpec wiring
def test_sparse_linear_auto_backend():
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 8, 64)).astype(np.float32))
    n_tokens = x.shape[0] * x.shape[1]
    spec = SparsitySpec(density=0.3, block=(16, 16), backend="auto",
                        bn=64, interpret=True, tune_n=n_tokens)
    params, meta = init_sparse_linear(0, 64, 96, spec, dtype=jnp.float32)
    # the warmed bucket is the one apply-time dispatch actually hits
    warmed = autotune.get_autotuner().pick(meta, n_tokens)
    assert warmed.source == "measured"
    y = apply_sparse_linear(params, meta, x, spec)
    assert y.shape == (2, 8, 96)
    ref_spec = SparsitySpec(density=0.3, block=(16, 16), backend="xla",
                            bn=64)
    y_ref = apply_sparse_linear(params, meta, x, ref_spec)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
