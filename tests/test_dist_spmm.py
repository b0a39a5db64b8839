"""Sharded SpMM execution tests (``launch.dist_spmm``).

Equivalence vs the single-device reference across shard counts {1, 2, 4, 8}
— forward within dtype tolerance and the VJP (dvals on the real support,
dB) — including ragged block-row counts, a partial trailing block-row, and
empty shards; plus the overlap chunk pipeline (bit-identical across chunk
depths, local and shard_map), the heavy-row guard and entry-granular
splits, the shard-count autotune axis (``resolve_n_shards`` determinism +
cache round-trip), the shard_bins occupancy invariants, the v7 autotune
fingerprint, the mixed-variant lax.switch path, and the model wiring
(``SparsitySpec(shards=...)`` including ``shards="auto"``).

shard_map cases need real devices: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``test-multidevice`` job does); on fewer devices they skip, the local-mode
equivalences still run.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcsr as bcsr_lib
from repro.core import permute, topology
from repro.core.sparse_linear import (SparsitySpec, apply_sparse_linear,
                                      init_sparse_linear,
                                      sparse_linear_specs)
from repro.kernels import autotune, ops
from repro.launch import dist_spmm

SHARD_COUNTS = (1, 2, 4, 8)


def _cases():
    """(name, BCSR) — ragged row count + partial trailing block-row, skewed
    power-law (empty element rows), and a clustered structure."""
    return [
        ("ragged_partial", bcsr_lib.random_bcsr(0, (23 * 16 + 5, 160),
                                                (16, 16), 0.3)),
        ("power_law_skew", bcsr_lib.from_scipy(
            topology.power_law(500, 5.0, seed=2), (16, 16))),
        ("clustered", bcsr_lib.from_scipy(
            topology.blocked_random(n=512, nnz_target=9000, cluster=16,
                                    seed=1), (16, 16))),
    ]


def _ref(a, b):
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    return arrays, meta, ops.spmm(arrays, meta, b, backend="xla")


def _b_for(a, n=48, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((a.shape[1], n)).astype(np.float32))


# ------------------------------------------------------------ bin assignment
def test_shard_bins_occupancy_invariants():
    """Every block-row lands in exactly one bin, cardinality caps hold, and
    the LPT loads beat (or match) a naive contiguous split on skew."""
    a = bcsr_lib.from_scipy(topology.power_law(800, 6.0, seed=3), (16, 16))
    a_p = a.ensure_nonempty_rows()
    bpr = np.diff(a_p.rowptr)
    for S in (2, 4, 8):
        rps = -(-a_p.n_block_rows // S)
        assign = permute.shard_bins(bpr, S, rows_per_shard=rps)
        assert assign.shape == (a_p.n_block_rows,)
        assert assign.min() >= 0 and assign.max() < S
        counts = np.bincount(assign, minlength=S)
        assert counts.max() <= rps
        assert counts.sum() == a_p.n_block_rows
        loads = np.asarray([bpr[assign == s].sum() for s in range(S)])
        assert loads.sum() == a_p.nnzb
        contig = np.asarray([bpr[s * rps:(s + 1) * rps].sum()
                             for s in range(S)])
        assert loads.max() <= contig.max()


def test_shard_bins_capacity_raises():
    with pytest.raises(ValueError, match="budget|capacity|cannot fit"):
        permute.shard_bins(np.asarray([10, 10, 10, 10]), 2,
                           rows_per_shard=2, max_load=12)


def test_prepare_sharded_budget_raises():
    a = bcsr_lib.random_bcsr(0, (128, 128), (16, 16), 0.5)
    with pytest.raises(ValueError):
        dist_spmm.prepare_sharded(a, 2, nnzb_per_shard=2)


def test_shard_balance_stats_beats_contiguous():
    a = bcsr_lib.from_scipy(topology.power_law(800, 6.0, seed=3), (16, 16))
    st = dist_spmm.shard_balance_stats(a, 4)
    assert st["imbalance"] <= st["contig_imbalance"] + 1e-9
    assert sum(st["loads"]) == st["nnzb"]


# ------------------------------------------------------- local-mode equality
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_fwd_matches_reference(n_shards, backend):
    for name, a in _cases():
        b = _b_for(a)
        _, _, ref = _ref(a, b)
        sharr, smeta = dist_spmm.prepare_sharded(a, n_shards,
                                                 dtype=jnp.float32)
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend=backend,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_sharded_grads_match_reference(n_shards):
    """dvals bit-comparable on the shared flat entry order; dB within fp
    tolerance (summation order differs across shards)."""
    a = bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), (16, 16))
    b = _b_for(a)
    arrays, meta, _ = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, n_shards, dtype=jnp.float32)

    def loss_sh(v, bb):
        out = dist_spmm.spmm_sharded(sharr._replace(vals=v), smeta, bb,
                                     backend="xla")
        return jnp.sum(out ** 2)

    def loss_ref(v, bb):
        arr = ops.SparseArrays(v, *arrays[1:])
        return jnp.sum(ops.spmm(arr, meta, bb, backend="xla") ** 2)

    gv, gb = jax.grad(loss_sh, argnums=(0, 1))(sharr.vals, b)
    rv, rb = jax.grad(loss_ref, argnums=(0, 1))(arrays.vals, b)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-4, atol=1e-3)


def test_empty_shards_more_shards_than_rows():
    a = bcsr_lib.random_bcsr(1, (30, 64), (16, 16), 0.5)  # 2 block-rows
    b = _b_for(a, n=8)
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 8, dtype=jnp.float32)
    out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_pre_reorder_composes_with_partition():
    """jaccard pre-permutation + partition: output still in ORIGINAL order."""
    a = bcsr_lib.from_scipy(
        topology.blocked_random(n=512, nnz_target=9000, cluster=16, seed=1),
        (16, 16))
    b = _b_for(a)
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32,
                                             reorder="jaccard")
    out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# ----------------------------------------------------- fingerprint (v7)
def test_fingerprint_shard_count_no_alias():
    a = bcsr_lib.random_bcsr(0, (256, 256), (16, 16), 0.2)
    _, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    k_full = autotune.fingerprint(meta, 64).key()
    k_shard = autotune.fingerprint(smeta.shard_metas[0], 64).key()
    assert k_full.startswith("v7|") and k_shard.startswith("v7|")
    assert "ns=1" in k_full and "ns=4" in k_shard
    # the key carries the row_loop schedule bound (v4 field) — real stats
    # on both sides
    assert f"mb={meta.max_bpr}" in k_full and meta.max_bpr > 0
    assert k_full != k_shard
    # v7: the chunk-depth field keys shard-count decisions; default nk=1
    assert k_full.endswith("|nk=1")
    k_chunked = autotune.fingerprint(meta, 64, n_chunks=4).key()
    assert k_chunked.endswith("|nk=4") and k_chunked != k_full


def test_tune_shards_caches_measured_picks():
    """tune_shards (the SparsitySpec(tune_n=...) path for sharded layers)
    must leave a measured entry under every shard fingerprint, and auto
    dispatch must then match the reference."""
    a = bcsr_lib.from_scipy(topology.power_law(400, 5.0, seed=2), (16, 16))
    b = _b_for(a, n=32)
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 2, dtype=jnp.float32)
    tuner = autotune.Autotuner()
    old = autotune.get_autotuner()
    autotune.set_autotuner(tuner)
    try:
        tuned = dist_spmm.tune_shards(sharr, smeta, 32, iters=1,
                                      interpret=True, tuner=tuner)
        for m in smeta.shard_metas:
            hit = tuner.get(autotune.fingerprint(m, 32))
            assert hit is not None and hit.source == "measured"
        assert tuned
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="auto",
                                     interpret=True)
    finally:
        autotune.set_autotuner(old)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_per_shard_auto_choices_resolve():
    a = bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), (16, 16))
    _, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    choices = dist_spmm._resolve_shard_choices(smeta, 64, "auto", 512)
    assert len(choices) == 4
    for be, bn in choices:
        assert be in ops.BACKENDS and bn >= 1


# --------------------------------------------------------- shard_map mode
def _mesh_or_skip(n_shards, col_shards=1):
    if jax.device_count() < n_shards * col_shards:
        pytest.skip(f"needs {n_shards * col_shards} devices "
                    f"(have {jax.device_count()}); run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return dist_spmm.make_spmm_mesh(n_shards, col_shards)


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_shard_map_matches_reference(n_shards):
    mesh = _mesh_or_skip(n_shards)
    for name, a in _cases():
        b = _b_for(a)
        _, _, ref = _ref(a, b)
        sharr, smeta = dist_spmm.prepare_sharded(a, n_shards,
                                                 dtype=jnp.float32)
        out = jax.jit(lambda v, bb, _s=sharr, _m=smeta, _me=mesh:
                      dist_spmm.spmm_sharded(_s._replace(vals=v), _m, bb,
                                             backend="xla", mesh=_me)
                      )(sharr.vals, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("n_shards", (2, 4, 8))
def test_shard_map_grads_match_reference(n_shards):
    mesh = _mesh_or_skip(n_shards)
    a = bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), (16, 16))
    b = _b_for(a)
    arrays, meta, _ = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, n_shards, dtype=jnp.float32)

    def loss_sh(v, bb):
        out = dist_spmm.spmm_sharded(sharr._replace(vals=v), smeta, bb,
                                     backend="pallas", interpret=True,
                                     mesh=mesh)
        return jnp.sum(out ** 2)

    def loss_ref(v, bb):
        arr = ops.SparseArrays(v, *arrays[1:])
        return jnp.sum(ops.spmm(arr, meta, bb, backend="xla") ** 2)

    gv, gb = jax.jit(jax.grad(loss_sh, argnums=(0, 1)))(sharr.vals, b)
    rv, rb = jax.grad(loss_ref, argnums=(0, 1))(arrays.vals, b)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-4, atol=1e-3)


def test_shard_map_2d_col_split():
    mesh = _mesh_or_skip(2, 2)
    a = bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), (16, 16))
    b = _b_for(a, n=50)          # N not divisible by col_shards: pads+trims
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 2, col_shards=2,
                                             dtype=jnp.float32)
    out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla", mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_mixed_variant_switch_dispatch():
    """Shards with different structure stats get DIFFERENT cached picks:
    the shard_map body must dispatch through lax.switch and still match
    the reference.  A well-balanced partition yields identical per-shard
    fingerprints (shared cache entry — by design), so this uses a skewed
    structure whose LPT bins genuinely differ."""
    mesh = _mesh_or_skip(2)
    dense = np.zeros((64, 512), np.float32)
    rng = np.random.default_rng(0)
    dense[:16, :480] = rng.standard_normal((16, 480))      # heavy block-row
    for r in range(1, 4):                                  # light rows
        dense[16 * r, 16 * r] = 1.0
    a = bcsr_lib.from_dense(dense, (16, 16))
    b = _b_for(a)
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 2, dtype=jnp.float32)
    fps = [autotune.fingerprint(m, 48).key() for m in smeta.shard_metas]
    assert fps[0] != fps[1]                   # stats really diverge
    tuner = autotune.Autotuner()
    for m, (variant, bn) in zip(smeta.shard_metas,
                                [("nnz_stream", 128), ("xla", 512)]):
        tuner.put(autotune.fingerprint(m, 48), autotune.KernelChoice(
            variant, bn, source="measured"), persist=False)
    old = autotune.get_autotuner()
    autotune.set_autotuner(tuner)
    try:
        choices = dist_spmm._resolve_shard_choices(smeta, 48, "auto", 512)
        assert len(set(choices)) > 1          # really a multi-branch switch
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="auto",
                                     interpret=True, mesh=mesh)
    finally:
        autotune.set_autotuner(old)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# --------------------------------------------- overlap chunking (pipeline)
def test_chunk_schedule_contract():
    """The schedule partitions [0, n) exactly; depth clamps to n."""
    assert dist_spmm.chunk_schedule(10, 4) == ((0, 3), (3, 6), (6, 9),
                                               (9, 10))
    assert dist_spmm.chunk_schedule(8, 1) == ((0, 8),)
    assert dist_spmm.chunk_schedule(2, 8) == ((0, 1), (1, 2))
    with pytest.raises(ValueError):
        dist_spmm.chunk_schedule(0, 2)
    with pytest.raises(ValueError):
        dist_spmm.chunk_schedule(8, 0)


@pytest.mark.parametrize("n_chunks", (2, 4))
def test_chunked_local_bitwise(n_chunks):
    """Chunked dispatch concatenates disjoint column panels: the result is
    BIT-identical to the unchunked run (the overlap contract)."""
    for name, a in _cases():
        b = _b_for(a)
        sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
        base = np.asarray(dist_spmm.spmm_sharded(sharr, smeta, b,
                                                 backend="xla"))
        out = np.asarray(dist_spmm.spmm_sharded(sharr, smeta, b,
                                                backend="xla",
                                                n_chunks=n_chunks))
        assert np.array_equal(out.view(np.uint32), base.view(np.uint32)), \
            f"{name}: nk={n_chunks} diverged from unchunked"


@pytest.mark.parametrize("n_chunks", (2, 4))
def test_chunked_shard_map_bitwise(n_chunks):
    """Under a real mesh the staged all-gather pipeline must still emit
    the exact unchunked bits."""
    mesh = _mesh_or_skip(4)
    a = bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), (16, 16))
    b = _b_for(a)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    base = np.asarray(dist_spmm.spmm_sharded(sharr, smeta, b,
                                             backend="xla", mesh=mesh))
    out = np.asarray(jax.jit(lambda bb: dist_spmm.spmm_sharded(
        sharr, smeta, bb, backend="xla", mesh=mesh,
        n_chunks=n_chunks))(b))
    assert np.array_equal(out.view(np.uint32), base.view(np.uint32))


def test_chunked_grads_route_through_unchunked_exec():
    """The chunked forward's custom VJP differentiates the unchunked exec:
    grads are bit-identical across chunk depths."""
    a = bcsr_lib.from_scipy(topology.power_law(300, 5.0, seed=2), (16, 16))
    b = _b_for(a)
    sharr, smeta = dist_spmm.prepare_sharded(a, 2, dtype=jnp.float32)

    def grads(k):
        def loss(v, bb):
            out = dist_spmm.spmm_sharded(sharr._replace(vals=v), smeta,
                                         bb, backend="xla", n_chunks=k)
            return jnp.sum(out ** 2)
        return jax.grad(loss, argnums=(0, 1))(sharr.vals, b)

    gv1, gb1 = grads(1)
    for k in (2, 4):
        gvk, gbk = grads(k)
        assert np.array_equal(np.asarray(gvk).view(np.uint32),
                              np.asarray(gv1).view(np.uint32))
        assert np.array_equal(np.asarray(gbk).view(np.uint32),
                              np.asarray(gb1).view(np.uint32))


# --------------------------------------- heavy rows: guard + entry splits
def _heavy_row_case():
    """One 64-block row towering over 3 single-block rows: under S=4 the
    balanced budget is ~18 blocks, so the heavy row alone blows it 3x."""
    dense = np.zeros((64, 1024), np.float32)
    rng = np.random.default_rng(0)
    dense[:16, :] = rng.standard_normal((16, 1024))
    for r in range(1, 4):
        dense[16 * r, 16 * r] = 1.0
    return bcsr_lib.from_dense(dense, (16, 16))


def test_heavy_row_overflow_raises():
    """Regression for the silent over-allocation: a block-row heavier than
    2x the balanced per-shard budget must raise, not quietly serialize."""
    a = _heavy_row_case()
    with pytest.raises(ValueError, match="heaviest block-row"):
        dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)


def test_split_heavy_rows_restores_balance():
    """split_heavy_rows=True fragments the heavy row across shards and the
    scatter-add combine reproduces the reference (allclose: the row's
    partial sums now accumulate across fragments)."""
    a = _heavy_row_case()
    b = _b_for(a)
    _, _, ref = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32,
                                             split_heavy_rows=True)
    assert smeta.n_split_fragments > 0
    assert sharr.split_src is not None and sharr.split_src.shape[0] > 0
    loads = [m.nnzb for m in smeta.shard_metas]
    assert max(loads) <= 2 * (-(-a.nnzb // 4) + smeta.rows_per_shard)
    for k in (1, 2, 4):
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla",
                                     n_chunks=k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=f"nk={k}")


def test_split_heavy_rows_vjp_matches_reference():
    a = _heavy_row_case()
    b = _b_for(a)
    arrays, meta, _ = _ref(a, b)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32,
                                             split_heavy_rows=True)

    def loss_sh(v, bb):
        out = dist_spmm.spmm_sharded(sharr._replace(vals=v), smeta, bb,
                                     backend="xla")
        return jnp.sum(out ** 2)

    def loss_ref(v, bb):
        arr = ops.SparseArrays(v, *arrays[1:])
        return jnp.sum(ops.spmm(arr, meta, bb, backend="xla") ** 2)

    gv, gb = jax.grad(loss_sh, argnums=(0, 1))(sharr.vals, b)
    rv, rb = jax.grad(loss_ref, argnums=(0, 1))(arrays.vals, b)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-4, atol=1e-3)


def test_split_heavy_rows_needs_derived_budget():
    """Entry splits re-derive per-shard budgets; a pinned nnzb_per_shard
    (the scan-stacking contract) cannot host fragments."""
    a = _heavy_row_case()
    with pytest.raises(ValueError, match="split_heavy_rows"):
        dist_spmm.prepare_sharded(a, 4, nnzb_per_shard=80,
                                  split_heavy_rows=True)


# ----------------------------------------- shard-count autotune (S="auto")
def test_resolve_n_shards_deterministic_and_structure_dependent():
    """Same structure -> same S (twice in-process); the skewed structure
    shards, the small uniform one does not (acceptance invariant)."""
    skew = bcsr_lib.from_scipy(topology.power_law(512, 5.0, seed=2),
                               (16, 16))
    uni = bcsr_lib.random_bcsr(0, (512, 256), (16, 16), 0.15)
    c1 = dist_spmm.resolve_n_shards(skew, n=64, max_shards=8, n_chunks=2)
    c2 = dist_spmm.resolve_n_shards(skew, n=64, max_shards=8, n_chunks=2)
    assert (c1.n_shards, c1.source) == (c2.n_shards, c2.source)
    assert c1.n_shards > 1
    assert dist_spmm.resolve_n_shards(uni, n=64, max_shards=8,
                                      n_chunks=2).n_shards == 1


def test_resolve_n_shards_deterministic_across_processes(tmp_path):
    """A subprocess building the same structure resolves the same S, and
    the decision round-trips through the REPRO_AUTOTUNE_CACHE JSON."""
    import json
    import os
    import subprocess
    import sys
    cache = tmp_path / "tune.json"
    prog = (
        "import numpy as np, jax.numpy as jnp\n"
        "from repro.core import bcsr as bcsr_lib, topology\n"
        "from repro.kernels import autotune, ops\n"
        "from repro.launch import dist_spmm\n"
        "a = bcsr_lib.from_scipy(topology.power_law(512, 5.0, seed=2),"
        " (16, 16))\n"
        "t = autotune.Autotuner()\n"
        "c = dist_spmm.resolve_n_shards(a, n=64, max_shards=8,"
        " n_chunks=2, tuner=t)\n"
        "fp = autotune.fingerprint(ops.prepare_sparse_meta(a), 64,"
        " n_chunks=2)\n"
        "t.put_shards(fp, 8, c, persist=True)\n"
        "print(c.n_shards, autotune.shard_entry_key(fp, 8))\n")
    env = {**os.environ, "REPRO_AUTOTUNE_CACHE": str(cache),
           "PYTHONPATH": os.pathsep.join(
               [p for p in sys.path if p.endswith("src")] +
               [os.environ.get("PYTHONPATH", "")])}
    outs = [subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, check=True)
            .stdout.split() for _ in range(2)]
    assert outs[0] == outs[1]
    s_sub, key = int(outs[0][0]), outs[0][1]
    here = dist_spmm.resolve_n_shards(
        bcsr_lib.from_scipy(topology.power_law(512, 5.0, seed=2), (16, 16)),
        n=64, max_shards=8, n_chunks=2, tuner=autotune.Autotuner())
    assert here.n_shards == s_sub
    # the persisted JSON loads back into a fresh tuner with the same pick
    data = json.loads(cache.read_text())
    assert key in data.get("shard_entries", {})
    fresh = autotune.Autotuner(cache_path=str(cache))
    a = bcsr_lib.from_scipy(topology.power_law(512, 5.0, seed=2), (16, 16))
    fp = autotune.fingerprint(ops.prepare_sparse_meta(a), 64, n_chunks=2)
    hit = fresh.get_shards(fp, 8)
    assert hit is not None and hit.n_shards == s_sub


def test_shard_key_chunk_depth_no_alias():
    """nk=1 and nk=2 shard decisions live under different cache keys: a
    deeper pipeline may justify a larger S (collective amortized)."""
    a = bcsr_lib.from_scipy(topology.power_law(512, 5.0, seed=2), (16, 16))
    meta = ops.prepare_sparse_meta(a)
    k1 = autotune.shard_entry_key(autotune.fingerprint(meta, 64), 8)
    k2 = autotune.shard_entry_key(
        autotune.fingerprint(meta, 64, n_chunks=2), 8)
    assert k1 != k2 and k1.startswith("shards|max=8|v7|")
    tuner = autotune.Autotuner()
    tuner.put_shards(autotune.fingerprint(meta, 64), 8,
                     autotune.ShardChoice(1), persist=False)
    assert tuner.get_shards(
        autotune.fingerprint(meta, 64, n_chunks=2), 8) is None


# ------------------------------------------------------------- model wiring
def _specs(shards=0):
    base = dict(density=0.3, block=(16, 16), backend="xla")
    return (SparsitySpec(**base),
            SparsitySpec(**base, shards=shards) if shards else None)


def test_sparse_linear_sharded_matches_unsharded():
    spec0, specS = _specs(shards=4)
    d, f = 96, 160
    p0, m0 = init_sparse_linear(11, d, f, spec0, dtype=jnp.float32)
    pS, mS = init_sparse_linear(11, d, f, specS, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 5, d)).astype(np.float32))
    y0 = apply_sparse_linear(p0, m0, x, spec0)
    yS = apply_sparse_linear(pS, mS, x, specS)
    np.testing.assert_allclose(np.asarray(yS), np.asarray(y0),
                               rtol=1e-5, atol=1e-4)

    def loss(v, p, m, s):
        return jnp.sum(apply_sparse_linear({**p, "vals": v}, m, x, s) ** 2)
    gS = jax.grad(loss)(pS["vals"], pS, mS, specS)
    g0 = jax.grad(loss)(p0["vals"], p0, m0, spec0)
    np.testing.assert_allclose(np.asarray(gS), np.asarray(g0),
                               rtol=1e-5, atol=1e-4)


def test_sparse_linear_specs_match_init_shapes():
    """The dims-only spec shapes are the contract that lets structures of
    DIFFERENT seeds scan-stack; init must land exactly on them."""
    _, specS = _specs(shards=4)
    d, f = 96, 160
    ps_specs, ms_specs = sparse_linear_specs(d, f, specS, dtype=jnp.float32)
    for seed in (11, 12, 13):
        pS, mS = init_sparse_linear(seed, d, f, specS, dtype=jnp.float32)
        assert set(pS) == set(ps_specs)
        for k in pS:
            assert ps_specs[k].shape == pS[k].shape, k
            assert ps_specs[k].dtype == pS[k].dtype, k
        assert ms_specs.rows_per_shard == mS.rows_per_shard
        assert ms_specs.nnzb_per_shard == mS.nnzb_per_shard


def test_sparse_linear_sharded_under_mesh():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    spec0, specS = _specs(shards=4)
    d, f = 96, 160
    p0, m0 = init_sparse_linear(11, d, f, spec0, dtype=jnp.float32)
    pS, mS = init_sparse_linear(11, d, f, specS, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 5, d)).astype(np.float32))
    y0 = apply_sparse_linear(p0, m0, x, spec0)
    mesh = dist_spmm.make_spmm_mesh(4)
    with dist_spmm.use_spmm_mesh(mesh):
        yS = jax.jit(lambda p, xx: apply_sparse_linear(p, mS, xx, specS)
                     )(pS, x)
    np.testing.assert_allclose(np.asarray(yS), np.asarray(y0),
                               rtol=1e-5, atol=1e-4)


def test_sparse_linear_auto_shards_resolves_statically():
    """shards="auto": the resolved S is a pure function of (dims, spec) —
    specs, init, and re-derivation agree; apply matches the unsharded
    path bit-for-bit at the default chunk depth."""
    from repro.core import sparse_linear as sl
    spec0, _ = _specs()
    specA = dataclasses.replace(spec0, shards="auto")
    d, f = 96, 160
    assert sl.is_sharded(specA) and not sl.is_sharded(spec0)
    s1 = sl.resolved_shards(specA, f, d)
    assert s1 == sl.resolved_shards(specA, f, d) and s1 >= 1
    ps_specs, _ = sparse_linear_specs(d, f, specA, dtype=jnp.float32)
    for seed in (11, 12):
        pA, mA = init_sparse_linear(seed, d, f, specA, dtype=jnp.float32)
        for k in pA:
            assert ps_specs[k].shape == pA[k].shape, k
    p0, m0 = init_sparse_linear(11, d, f, spec0, dtype=jnp.float32)
    pA, mA = init_sparse_linear(11, d, f, specA, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 5, d)).astype(np.float32))
    y0 = np.asarray(apply_sparse_linear(p0, m0, x, spec0))
    yA = np.asarray(apply_sparse_linear(pA, mA, x, specA))
    np.testing.assert_allclose(yA, y0, rtol=1e-5, atol=1e-4)
    # chunk depth is spec-controlled and value-preserving
    spec1 = dataclasses.replace(specA, shard_chunks=1)
    y1 = np.asarray(apply_sparse_linear(pA, mA, x, spec1))
    assert np.array_equal(yA.view(np.uint32), y1.view(np.uint32))


def test_model_mlp_sharded_matches_dense_path():
    """cfg.ffn_sparsity.shards wires through init_mlp/mlp unchanged."""
    from repro.configs import get_config
    from repro.models import layers as L
    cfg0 = dataclasses.replace(get_config("smat-ffn-1.3b:smoke"),
                               dtype="float32")
    specS = dataclasses.replace(cfg0.ffn_sparsity, shards=2)
    cfgS = dataclasses.replace(cfg0, ffn_sparsity=specS)
    key = jax.random.PRNGKey(0)
    p0 = L.init_mlp(cfg0, key, jnp.float32, seed_hint=3)
    pS = L.init_mlp(cfgS, key, jnp.float32, seed_hint=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg0.d_model),
                          jnp.float32)
    y0 = L.mlp(cfg0, p0, x)
    yS = L.mlp(cfgS, pS, x)
    np.testing.assert_allclose(np.asarray(yS), np.asarray(y0),
                               rtol=1e-4, atol=1e-4)
