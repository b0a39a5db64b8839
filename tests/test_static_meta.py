"""Static structure-metadata pipeline tests (specs-vs-init contract,
model-path heterogeneous per-shard dispatch, reorder-aware ``max_bpr``,
v6 fingerprints).

The contract under test: a sparse layer's TRUE structure meta is a pure
static function of ``(seed, dims, spec)`` — ``sparse_linear_meta`` (and
``sparse_linear_specs(..., seed=...)``) must reproduce exactly what
``init_sparse_linear`` returns, and the model path (``models.layers.mlp``)
must dispatch on those metas rather than dims-only stand-ins, so
``SparsitySpec(shards=S)`` gets the same per-shard autotune picks as the
raw ``launch.dist_spmm`` API.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import bcsr as bcsr_lib
from repro.core import topology
from repro.core.sparse_linear import (SparsitySpec, init_sparse_linear,
                                      merge_sparse_metas, shard_shapes,
                                      sparse_linear_meta,
                                      sparse_linear_specs, _pattern_for)
from repro.kernels import autotune, ops
from repro.launch import dist_spmm
from repro.models import layers as L
from repro.models import transformer as T

D, F = 96, 160


def _spec(shards=0, reorder="identity", backend="xla"):
    return SparsitySpec(density=0.3, block=(16, 16), backend=backend,
                        reorder=reorder, shards=shards, interpret=True)


# ------------------------------------------------------ specs-vs-init contract
@pytest.mark.parametrize("shards", [0, 1, 4])
@pytest.mark.parametrize("reorder", ["identity", "jaccard"])
def test_specs_meta_matches_init_meta(shards, reorder):
    """The same (seed, dims, spec) must yield the SAME meta through all
    three derivations: init (params + meta), the memoized static path,
    and the seeded specs path — across seeds, shard counts, reorder."""
    spec = _spec(shards=shards, reorder=reorder)
    for seed in (3, 11, 42):
        _, m_init = init_sparse_linear(seed, D, F, spec, dtype=jnp.float32)
        _, m_specs = sparse_linear_specs(D, F, spec, dtype=jnp.float32,
                                         seed=seed)
        assert m_specs == m_init
        assert sparse_linear_meta(seed, D, F, spec) == m_init
        if shards > 0:
            assert all(m.max_bpr > 0 for m in m_init.shard_metas)
        else:
            assert m_init.max_bpr > 0


def test_seedless_specs_stay_dims_only():
    """Back-compat: without a seed the specs meta carries zero stats (the
    allocation-free dry-run mode) and the param specs are unchanged."""
    spec = _spec(shards=4)
    p_plain, m_plain = sparse_linear_specs(D, F, spec, dtype=jnp.float32)
    p_seeded, m_seeded = sparse_linear_specs(D, F, spec, dtype=jnp.float32,
                                             seed=7)
    assert all(m.max_bpr == 0 for m in m_plain.shard_metas)
    assert jax.tree.map(lambda s: (s.shape, s.dtype), p_plain) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), p_seeded)
    assert any(m.max_bpr > 0 for m in m_seeded.shard_metas)


# -------------------------------------------------------------- meta merging
def test_merge_sparse_metas_takes_stats_max():
    spec = _spec()
    metas = [sparse_linear_meta(s, D, F, spec) for s in (1, 2, 3)]
    merged = merge_sparse_metas(metas)
    assert merged.max_bpr == max(m.max_bpr for m in metas)
    assert merged.bpr_cv_pct == max(m.bpr_cv_pct for m in metas)
    assert merged.nnzb == metas[0].nnzb        # static fields preserved


def test_merge_sparse_metas_shard_wise():
    spec = _spec(shards=4)
    metas = [sparse_linear_meta(s, D, F, spec) for s in (1, 2, 3)]
    merged = merge_sparse_metas(metas)
    for s in range(4):
        assert merged.shard_metas[s].max_bpr == \
            max(m.shard_metas[s].max_bpr for m in metas)


def test_merge_sparse_metas_rejects_mismatched_structure():
    spec = _spec()
    m0 = sparse_linear_meta(1, D, F, spec)
    m1 = sparse_linear_meta(1, D, F + 32, spec)
    with pytest.raises(ValueError, match="static structure"):
        merge_sparse_metas([m0, m1])


# ------------------------------------------------- model path == direct API
def test_model_path_shard_metas_match_direct_dist_spmm():
    """SparsitySpec(shards=4) through mlp(): the static metas the model
    path dispatches on are EXACTLY the ShardedMetas the raw dist_spmm API
    builds for the same patterns — so per-shard picks are identical."""
    spec = _spec(shards=4, backend="auto")
    meta_in, meta_out = L.mlp_sparse_metas(spec, D, F, (0,))

    def direct(seed, in_dim, out_dim):
        a = _pattern_for(seed, in_dim, out_dim, spec)
        rps, nnzb_ps, _ = shard_shapes(spec, out_dim, in_dim)
        _, m = dist_spmm.prepare_sharded(
            a, spec.shards, col_shards=spec.shard_cols, dtype=jnp.float32,
            reorder=spec.reorder, rows_per_shard=rps,
            nnzb_per_shard=nnzb_ps)
        return m

    seed = L.mlp_seed(0)
    m_gate = direct(seed, D, F)
    m_up = direct(seed + 1, D, F)
    m_down = direct(seed + 2, F, D)
    assert meta_in == merge_sparse_metas([m_gate, m_up])
    assert meta_out == m_down
    for n in (8, 64, 512):
        picks_model = [ops.resolve_backend("auto", spec.bn, m, n)
                       for m in meta_out.shard_metas]
        picks_direct = [ops.resolve_backend("auto", spec.bn, m, n)
                        for m in m_down.shard_metas]
        assert picks_model == picks_direct


def test_model_path_shard_fingerprints_differ():
    """Regression vs the dims-only collapse: shards with different local
    structures must reach the autotuner as DIFFERENT v6 fingerprints
    through the model path (they used to share one zero-stats key)."""
    spec = _spec(shards=4, backend="auto")
    meta_in, meta_out = L.mlp_sparse_metas(spec, D, F, (0,))
    for meta in (meta_in, meta_out):
        keys = {autotune.fingerprint(m, 64).key() for m in meta.shard_metas}
        assert len(keys) >= 2, keys


def test_model_path_heterogeneous_picks_execute():
    """End-to-end: a tuner seeded with DIFFERENT per-shard picks drives
    the model path through the multi-branch dispatch, and the output
    matches the xla-only reference bit-for-tolerance."""
    cfg = dataclasses.replace(get_config("smat-ffn-1.3b:smoke"),
                              dtype="float32", d_model=D, d_ff=F)
    spec_auto = _spec(shards=4, backend="auto")
    spec_xla = dataclasses.replace(spec_auto, backend="xla")
    cfg_auto = dataclasses.replace(cfg, ffn_sparsity=spec_auto)
    cfg_xla = dataclasses.replace(cfg, ffn_sparsity=spec_xla)

    params = L.init_mlp(cfg_auto, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, D), jnp.float32)
    n_tokens = 2 * 4

    meta_in, meta_out = L.mlp_sparse_metas(spec_auto, D, F, (0,))
    tuner = autotune.Autotuner()
    variants = [("nnz_stream", 128), ("xla", 512)]
    for meta in (meta_in, meta_out):
        fps = []
        for m in meta.shard_metas:
            fp = autotune.fingerprint(m, n_tokens)
            if fp.key() not in [f.key() for f in fps]:
                fps.append(fp)
        assert len(fps) >= 2          # structures genuinely diverge
        for i, fp in enumerate(fps):
            v, bn = variants[i % len(variants)]
            tuner.put(fp, autotune.KernelChoice(v, bn, source="measured"),
                      persist=False)

    old = autotune.get_autotuner()
    autotune.set_autotuner(tuner)
    try:
        for meta in (meta_in, meta_out):
            choices = dist_spmm._resolve_shard_choices(
                meta, n_tokens, "auto", spec_auto.bn)
            # picks did NOT collapse to one streaming choice
            assert len(set(choices)) >= 2, choices
        y_auto = L.mlp(cfg_auto, params, x)
    finally:
        autotune.set_autotuner(old)
    y_ref = L.mlp(cfg_xla, params, x)
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_stacked_model_forward_auto_matches_xla():
    """The merged stack meta must be valid for EVERY scanned layer: a full
    2-layer sparse-FFN forward under backend='auto' (real stats) matches
    the xla-backend forward on the same params."""
    cfg0 = dataclasses.replace(get_config("smat-ffn-1.3b:smoke"),
                               dtype="float32")
    spec_auto = dataclasses.replace(cfg0.ffn_sparsity, backend="auto")
    cfg_auto = dataclasses.replace(cfg0, ffn_sparsity=spec_auto)
    cfg_xla = dataclasses.replace(
        cfg0, ffn_sparsity=dataclasses.replace(spec_auto, backend="xla"))
    params = T.init_params(cfg_auto, seed=0)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg0.vocab_size, (1, 8)),
        jnp.int32)}
    la, _, _ = T.forward(cfg_auto, params, batch)
    lx, _, _ = T.forward(cfg_xla, params, batch)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lx),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------ reorder-aware max_bpr
def test_reorder_strictly_shrinks_row_loop_schedule():
    """Acceptance: on a clustered structure, the jaccard permutation gives
    a STRICTLY smaller ``max_bpr`` than identity order, and the streamed
    kernel on the reordered structure still computes the right answer."""
    csr = topology.blocked_random(n=512, nnz_target=9000, cluster=16, seed=1)
    a = bcsr_lib.from_scipy(csr, (16, 16))
    m_id = ops.prepare_sparse_meta(a)
    m_ro = ops.prepare_sparse_meta(a, reorder="jaccard")
    assert m_ro.max_bpr < m_id.max_bpr
    assert m_ro.nnzb <= m_id.nnzb

    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32,
                                      reorder="jaccard")
    assert meta == m_ro       # prepare vs meta-only: bit-identical
    b = jnp.asarray(np.random.default_rng(0).standard_normal(
        (meta.shape[1], 32)).astype(np.float32))
    y_ro = ops.spmm(arrays, meta, b, backend="pallas", interpret=True)
    arr_id, meta_id = ops.prepare_sparse(a, dtype=jnp.float32)
    y_ref = ops.spmm(arr_id, meta_id, b, backend="xla")
    np.testing.assert_allclose(np.asarray(y_ro), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


def test_fingerprint_carries_schedule_bound():
    """Two metas identical except for the schedule bound ``max_bpr`` must
    not alias in the cache (the mb= field, added in v4), so a shrunk
    reordered structure never inherits the unshrunk twin's fused-attention
    decision."""
    a = bcsr_lib.random_bcsr_exact(0, (256, 256), (16, 16), nnzb=64)
    meta = ops.prepare_sparse_meta(a)
    twin = dataclasses.replace(meta, max_bpr=meta.max_bpr + 1)
    k0, k1 = autotune.fingerprint(meta, 64).key(), \
        autotune.fingerprint(twin, 64).key()
    assert k0 != k1
    assert k0.startswith("v7|") and f"mb={meta.max_bpr}" in k0
