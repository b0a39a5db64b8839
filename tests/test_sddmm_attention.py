"""The SDDMM + block-sparse attention subsystem (PR 5).

Covers: the public ``ops.sddmm`` (forward/VJP parity vs the dense masked
reference across backends, reorder transparency), the v7 ``op=``
fingerprint contract (SpMM and SDDMM picks never alias — pinned exactly),
the mask builders, ``block_sparse_attention`` forward/backward vs the
dense-masked oracle across backends and mask specs, the ``dist_spmm`` row
sharding of the score structure (in-process AND shard_map when >= 4
devices are available — the CI ``test-multidevice`` job forces 8), and
the end-to-end wiring (transformer flag, ServeEngine decode, dryrun
report).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcsr as bcsr_lib
from repro.kernels import autotune, ops
from repro.models import attention as A


@pytest.fixture(autouse=True)
def _fresh_tuner():
    autotune.set_autotuner(autotune.Autotuner())
    yield
    autotune.set_autotuner(None)


def _mask_cfg(mask=None, backend="xla", **kw):
    return A.AttnSparsitySpec(mask=mask or A.banded(24), block=(8, 8),
                              backend=backend, interpret=True, **kw)


# ================================================================= ops.sddmm
def _mk(shape=(96, 128), block=(16, 16), density=0.3, seed=0):
    return bcsr_lib.random_bcsr(seed, shape, block,
                                density).ensure_nonempty_rows()


def _sddmm_dense_oracle(arrays, meta, x, y):
    h, w = meta.block
    M, K = meta.shape
    xp = x
    if meta.reorder != "identity" and arrays.row_perm is not None:
        xp = jnp.take(x, arrays.row_perm, axis=0)
    full = jnp.pad(xp, ((0, meta.n_block_rows * h - M), (0, 0))) @ \
        jnp.pad(y, ((0, meta.n_block_cols * w - K), (0, 0))).T
    blocks = full.reshape(meta.n_block_rows, h, meta.n_block_cols, w
                          ).transpose(0, 2, 1, 3)
    samp = blocks[np.asarray(arrays.row_ids), np.asarray(arrays.col_ids)]
    return samp * np.asarray(arrays.real_mask)[:, None, None]


@pytest.mark.parametrize("backend", ["auto", "xla", "pallas", "row_loop",
                                     "dense"])
def test_ops_sddmm_forward(backend):
    a = _mk()
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((96, 40)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((128, 40)).astype(np.float32))
    got = ops.sddmm(arrays, meta, x, y, backend=backend, bn=64,
                    interpret=True)
    want = _sddmm_dense_oracle(arrays, meta, x, y)
    assert got.shape == (meta.nnzb,) + tuple(meta.block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_sddmm_grads_match_dense(backend):
    a = _mk(shape=(64, 96), density=0.4)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, 24)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((96, 24)).astype(np.float32))

    def loss(x, y):
        out = ops.sddmm(arrays, meta, x, y, backend=backend, bn=64,
                        interpret=True)
        return jnp.sum(out * out)

    def loss_dense(x, y):
        return jnp.sum(_sddmm_dense_oracle(arrays, meta, x, y) ** 2)

    gx, gy = jax.grad(loss, (0, 1))(x, y)
    gx_d, gy_d = jax.grad(loss_dense, (0, 1))(x, y)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(gy_d),
                               rtol=1e-3, atol=1e-3)


def test_ops_sddmm_reorder_transparent():
    """A jaccard-reordered structure samples (P X) Y^T — callers keep
    passing original-order X, grads match the dense oracle."""
    a = _mk(density=0.25, seed=3)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32,
                                      reorder="jaccard")
    assert meta.reorder == "jaccard"
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((96, 24)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((128, 24)).astype(np.float32))
    got = ops.sddmm(arrays, meta, x, y, backend="pallas", bn=64,
                    interpret=True)
    want = _sddmm_dense_oracle(arrays, meta, x, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    gx, gy = jax.grad(lambda x, y: jnp.sum(ops.sddmm(
        arrays, meta, x, y, backend="pallas", bn=64, interpret=True) ** 2),
        (0, 1))(x, y)
    gx_d, gy_d = jax.grad(lambda x, y: jnp.sum(
        _sddmm_dense_oracle(arrays, meta, x, y) ** 2), (0, 1))(x, y)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(gy_d),
                               rtol=1e-3, atol=1e-3)


def test_spmm_sddmm_mutual_duals_second_order():
    """spmm's VJP runs sddmm and vice versa — second-order AD bounces
    between the two custom VJPs.  Pinned on the xla backend (the pure-jnp
    kernels differentiate to any order; interpret-mode Pallas kernels with
    scalar-prefetch grids have no JVP rule, so the dual chain's LEAVES cap
    the order there, not the chain itself)."""
    a = _mk(shape=(32, 32), block=(8, 8), density=0.5)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    rng = np.random.default_rng(4)
    b = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))

    def f(b):
        return jnp.sum(ops.spmm(arrays, meta, b, backend="xla") ** 3)

    hvp = jax.grad(lambda b: jnp.vdot(jax.grad(f)(b), b))(b)
    # oracle: same HVP through the dense equivalent
    dense = jnp.asarray(a.to_dense())

    def fd(b):
        return jnp.sum((dense @ b) ** 3)

    hvp_d = jax.grad(lambda b: jnp.vdot(jax.grad(fd)(b), b))(b)
    np.testing.assert_allclose(np.asarray(hvp), np.asarray(hvp_d),
                               rtol=1e-3, atol=1e-3)


# ======================================================= v7 fingerprint pins
def test_v6_key_format_pinned():
    """The exact v7 key layout — a cross-process cache contract."""
    fp = autotune.Fingerprint(
        n_block_rows=4, n_block_cols=5, block=(16, 16), nnzb=10,
        pad_bucket=1, skew_bucket=2, n_bucket=64, reorder="jaccard",
        n_shards=2, max_bpr=3, op="sddmm")
    assert fp.key() == ("v7|op=sddmm|nbr=4|nbc=5|b=16x16|nnzb=10|pad=1"
                        "|skew=2|n=64|ro=jaccard|ns=2|mb=3|nk=1")
    assert dataclasses.replace(fp, op="spmm").key() == (
        "v7|op=spmm|nbr=4|nbc=5|b=16x16|nnzb=10|pad=1"
        "|skew=2|n=64|ro=jaccard|ns=2|mb=3|nk=1")
    assert dataclasses.replace(fp, n_chunks=4).key().endswith("|nk=4")


def test_spmm_and_sddmm_keys_never_alias():
    a = _mk()
    meta = ops.prepare_sparse_meta(a)
    fp_spmm = autotune.fingerprint(meta, 64)
    fp_sddmm = autotune.fingerprint(meta, 64, op="sddmm")
    assert fp_spmm.key() != fp_sddmm.key()
    assert fp_spmm.key().startswith("v7|op=spmm|")
    assert fp_sddmm.key().startswith("v7|op=sddmm|")
    # a cached pick for one family is invisible to the other
    tuner = autotune.get_autotuner()
    tuner.put(fp_spmm, autotune.KernelChoice("xla", 512), persist=False)
    assert tuner.get(fp_sddmm) is None


def test_variant_families_disjoint():
    spmm_names = set(autotune.variant_names("spmm"))
    sddmm_names = set(autotune.variant_names("sddmm"))
    assert spmm_names == {"nnz_stream", "row_loop", "xla", "dense"}
    assert sddmm_names == {"sddmm_stream", "sddmm_row_loop", "sddmm_xla",
                           "sddmm_dense"}
    attn_names = set(autotune.variant_names("attn"))
    assert attn_names == {"attn_fused", "attn_composed"}
    assert not (spmm_names & sddmm_names) and not (attn_names &
                                                   (spmm_names | sddmm_names))
    assert set(autotune.variant_names(None)) == \
        spmm_names | sddmm_names | attn_names


def test_auto_pick_stays_in_family():
    a = _mk()
    meta = ops.prepare_sparse_meta(a)
    for n in (8, 64, 512):
        pick = autotune.get_autotuner().pick(meta, n, op="sddmm")
        assert pick.variant in autotune.variant_names("sddmm")
        pick_s = autotune.get_autotuner().pick(meta, n)
        assert pick_s.variant in autotune.variant_names("spmm")


def test_tune_sddmm_measured_and_persisted(tmp_path):
    a = _mk(shape=(64, 64), density=0.4)
    cache = str(tmp_path / "tuned.json")
    tuner = autotune.Autotuner(cache_path=cache)
    choice, timings = tuner.tune(a, 16, op="sddmm", iters=1,
                                 interpret=True)
    assert choice.variant in autotune.variant_names("sddmm")
    assert choice.source == "measured"
    assert timings
    # winner lands under the v7 op=sddmm key and reloads from disk
    fp = autotune.fingerprint_bcsr(a.ensure_nonempty_rows(), 16, op="sddmm")
    fresh = autotune.Autotuner(cache_path=cache)
    assert fresh.get(fp) == choice


# ============================================================== mask builders
def test_mask_builders_structure():
    L, blk = 128, (16, 16)
    m_causal = A.attention_mask_meta(A.blockwise_causal(), L, blk)
    nbr = m_causal.n_block_rows
    assert m_causal.nnzb == nbr * (nbr + 1) // 2      # dense causal blocks
    m_band = A.attention_mask_meta(A.banded(32), L, blk)
    assert m_band.nnzb < m_causal.nnzb
    assert m_band.max_bpr == 3                        # ceil((32+16)/16)
    m_lg = A.attention_mask_meta(A.local_global(32, 16), L, blk)
    assert m_band.nnzb < m_lg.nnzb < m_causal.nnzb
    with pytest.raises(ValueError):
        A.banded(0)


def test_mask_meta_matches_arrays_and_merges():
    spec = A.banded(24)
    arrays, meta = A.attention_mask_arrays(spec, 64, (8, 8))
    assert meta == A.attention_mask_meta(spec, 64, (8, 8))
    assert arrays.vals.shape[0] == meta.nnzb
    merged = A.merged_attention_meta([spec, spec], 64, (8, 8))
    assert merged == meta


# ===================================================== block-sparse attention
def _qkv(B=2, L=64, H=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, L, H, d)), jnp.float32)
    return mk(), mk(), mk()


def _dense_masked_attention(q, k, v, mask, scale=None, cap=None):
    B, L, H, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    pos = jnp.arange(L)
    ok = A.mask_allowed(mask, pos, pos)
    s = jnp.einsum("blhd,bshd->bhls", q, k) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    s = jnp.where(ok[None, None], s, A.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhls,bshd->blhd", p, v)


@pytest.mark.parametrize("backend", ["auto", "xla", "pallas"])
@pytest.mark.parametrize("mask", [A.banded(24), A.local_global(16, 8),
                                  A.blockwise_causal()],
                         ids=["banded", "local_global", "causal"])
def test_attention_forward_matches_dense_masked(backend, mask):
    q, k, v = _qkv()
    spec = _mask_cfg(mask, backend=backend)
    out = A.block_sparse_attention(q, k, v, spec)
    want = _dense_masked_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["auto", "xla", "pallas"])
@pytest.mark.parametrize("mask", [A.banded(24), A.local_global(16, 8)],
                         ids=["banded", "local_global"])
def test_attention_grads_match_dense_masked(backend, mask):
    q, k, v = _qkv()
    spec = _mask_cfg(mask, backend=backend)
    g = jax.grad(lambda q, k, v: jnp.sum(
        A.block_sparse_attention(q, k, v, spec) ** 2), (0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda q, k, v: jnp.sum(
        _dense_masked_attention(q, k, v, mask) ** 2), (0, 1, 2))(q, k, v)
    for got, want, name in zip(g, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_attention_softcap_and_scale():
    q, k, v = _qkv(seed=5)
    mask = A.banded(16)
    out = A.block_sparse_attention(q, k, v, _mask_cfg(mask), scale=0.25,
                                   cap=5.0)
    want = _dense_masked_attention(q, k, v, mask, scale=0.25, cap=5.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_block_softmax_rows_sum_to_one():
    arrays, meta = A.attention_mask_arrays(A.banded(24), 64, (8, 8))
    rng = np.random.default_rng(6)
    scores = jnp.asarray(rng.standard_normal(
        (meta.nnzb,) + tuple(meta.block)), jnp.float32)
    elem = (arrays.vals > 0.5) & arrays.real_mask[:, None, None]
    probs = A.block_softmax(scores, elem, arrays.row_ids,
                            meta.n_block_rows)
    assert bool(jnp.all(probs >= 0))
    assert np.all(np.asarray(probs)[~np.asarray(elem)] == 0)
    row_sums = jax.ops.segment_sum(probs.sum(axis=2), arrays.row_ids,
                                   num_segments=meta.n_block_rows)
    np.testing.assert_allclose(np.asarray(row_sums), 1.0, rtol=1e-5)


# ==================================================== sharded score structure
def test_attention_sharded_scores_local_fallback():
    q, k, v = _qkv()
    mask = A.banded(24)
    want = A.block_sparse_attention(q, k, v, _mask_cfg(mask))
    out = A.block_sparse_attention(q, k, v, _mask_cfg(mask, shards=4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # grads flow through the per-shard VJPs + outer gather
    g = jax.grad(lambda q: jnp.sum(A.block_sparse_attention(
        q, k, v, _mask_cfg(mask, shards=4)) ** 2))(q)
    g_ref = jax.grad(lambda q: jnp.sum(A.block_sparse_attention(
        q, k, v, _mask_cfg(mask)) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >= 4 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_attention_sharded_scores_shard_map():
    from repro.launch import dist_spmm
    q, k, v = _qkv()
    spec = _mask_cfg(A.banded(24), shards=4)
    want = A.block_sparse_attention(q, k, v, spec)    # local fallback
    mesh = dist_spmm.make_spmm_mesh(4)
    with dist_spmm.use_spmm_mesh(mesh):
        out = jax.jit(lambda q, k, v: A.block_sparse_attention(
            q, k, v, spec))(q, k, v)
        g = jax.grad(lambda q: jnp.sum(A.block_sparse_attention(
            q, k, v, spec) ** 2))(q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(lambda q: jnp.sum(A.block_sparse_attention(
        q, k, v, spec) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


# ============================================================== model wiring
def _smoke_cfg(**attn_kw):
    from repro.configs.archs import ARCHS, smoke_config
    cfg = smoke_config(ARCHS["smat-attn-1.3b"])
    cfg = dataclasses.replace(cfg, dtype="float32")
    if attn_kw:
        cfg = dataclasses.replace(cfg, attn_sparsity=dataclasses.replace(
            cfg.attn_sparsity, **attn_kw))
    return cfg


def test_transformer_causal_sparse_equals_dense():
    from repro.models import transformer as T
    cfg = _smoke_cfg(mask=A.blockwise_causal())
    cfg_dense = dataclasses.replace(cfg, attn_sparsity=None)
    params = T.init_params(cfg, seed=0)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 64)), jnp.int32)}
    l_sparse, _, _ = T.forward(cfg, params, batch)
    l_dense, _, _ = T.forward(cfg_dense, params, batch)
    np.testing.assert_allclose(np.asarray(l_sparse), np.asarray(l_dense),
                               rtol=1e-4, atol=1e-4)


def test_transformer_banded_equals_sliding_window():
    from repro.models import transformer as T
    cfg = _smoke_cfg(mask=A.banded(32))
    cfg_swa = dataclasses.replace(cfg, attn_sparsity=None,
                                  sliding_window=32)
    params = T.init_params(cfg, seed=0)
    batch = {"tokens": jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 64)), jnp.int32)}
    l_sparse, _, _ = T.forward(cfg, params, batch)
    l_swa, _, _ = T.forward(cfg_swa, params, batch)
    np.testing.assert_allclose(np.asarray(l_sparse), np.asarray(l_swa),
                               rtol=1e-4, atol=1e-4)


def test_transformer_train_grads_finite():
    from repro.models import transformer as T
    cfg = _smoke_cfg()
    params = T.init_params(cfg, seed=0)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 64)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    loss, _ = T.train_loss(cfg, params, batch, remat="full")
    g = jax.grad(lambda p: T.train_loss(cfg, p, batch, remat="full")[0],
                 allow_int=True)(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(g):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf)))


def test_serve_decode_consistent_with_dense():
    """ServeEngine decode traces through the sparse-mask bias: with the
    blockwise-causal mask (== plain causal) the served tokens must match a
    dense-attention engine exactly."""
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine
    cfg = _smoke_cfg(mask=A.blockwise_causal())
    cfg_dense = dataclasses.replace(cfg, attn_sparsity=None)
    params = T.init_params(cfg, seed=0)
    prompts = [np.asarray([5, 6, 7, 11]), np.asarray([9, 2])]

    def run(c):
        eng = ServeEngine(c, params, n_slots=2, cache_len=64)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        done = eng.run()
        return {r: done[r].out_tokens for r in done}

    assert run(cfg) == run(cfg_dense)


def test_dryrun_attention_report():
    from repro.launch import dryrun
    cfg = _smoke_cfg()
    rep = dryrun.sparse_attention_report(cfg, seq_len=128)
    assert rep["nnzb"] > 0 and rep["max_bpr"] > 0
    assert rep["mask"]["kind"] == "banded"
    assert 0 < rep["block_density_vs_causal"] <= 1.0
    assert rep["sddmm_pick"].split("/")[0] in ops.BACKENDS
    assert rep["spmm_pick"].split("/")[0] in ops.BACKENDS
    # dense archs without the flag report nothing
    assert dryrun.sparse_attention_report(
        dataclasses.replace(cfg, attn_sparsity=None)) == {}


def test_long_context_applicability():
    """A bounded sparse mask qualifies for the 500k decode cell; the
    blockwise-causal anchor does not."""
    from repro.configs.base import SHAPES, cell_applicable
    cfg = _smoke_cfg(mask=A.banded(32))
    ok, _ = cell_applicable(cfg, SHAPES["long_500k"])
    assert ok
    cfg_c = _smoke_cfg(mask=A.blockwise_causal())
    ok, _ = cell_applicable(cfg_c, SHAPES["long_500k"])
    assert not ok
