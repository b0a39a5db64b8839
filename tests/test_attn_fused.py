"""Fused one-kernel block-sparse attention (PR 6): the bit-for-bit f32
forward pin against the composed SDDMM -> block_softmax -> SpMM triple
across all three mask families, gradient parity through the composed VJP,
the v6 ``op=attn`` fingerprint non-aliasing contract, and the
attention-level dispatch rules (``backend="auto"``/``"fused"``; sharded
and explicit-kernel specs stay composed).

Runs unchanged under forced multi-host-device CI
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the fused
kernel is per-instance math; the sharded-spec test exercises the
composed fallback path those devices feed."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import autotune, bcsr_attn
from repro.models import attention as A


@pytest.fixture(autouse=True)
def _fresh_tuner():
    autotune.set_autotuner(autotune.Autotuner())
    yield
    autotune.set_autotuner(None)


MASKS = {
    "banded": A.banded(24),
    "local_global": A.local_global(16, 8),
    "blockwise_causal": A.blockwise_causal(),
}


def _qkv(L, d, B=2, H=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, L, H, d)), jnp.float32)
                 for _ in range(3))


def _specs(mask, block=(8, 8)):
    fused = A.AttnSparsitySpec(mask=mask, block=block, backend="fused",
                               interpret=True)
    composed = A.AttnSparsitySpec(mask=mask, block=block, backend="xla")
    return fused, composed


# ===================================================== bit-for-bit forward
@pytest.mark.parametrize("mask_kind", list(MASKS))
@pytest.mark.parametrize("L", [64, 61])   # aligned + ragged tail block-row
def test_fused_forward_bitwise_equals_composed(mask_kind, L):
    """The tentpole pin: fused forward == composed forward BIT-FOR-BIT in
    f32 — not allclose — across mask families, including ragged tails
    whose padded query rows have no valid element."""
    q, k, v = _qkv(L, 8, seed=hash(mask_kind) % 1000)
    spec_f, spec_c = _specs(MASKS[mask_kind])
    got = A.block_sparse_attention(q, k, v, spec_f)
    want = A.block_sparse_attention(q, k, v, spec_c)
    assert got.dtype == want.dtype == jnp.float32
    assert bool(jnp.all(got == want)), (
        f"max abs diff {float(jnp.max(jnp.abs(got - want)))}")


def test_fused_bitwise_vs_composed_pallas_backend():
    """Same pin against the composed path on its Pallas (interpret)
    kernels — the production composed arm, not just the xla oracle."""
    q, k, v = _qkv(64, 16)
    spec_f, _ = _specs(A.banded(24))
    spec_p = A.AttnSparsitySpec(mask=A.banded(24), block=(8, 8),
                                backend="pallas", interpret=True)
    got = A.block_sparse_attention(q, k, v, spec_f)
    want = A.block_sparse_attention(q, k, v, spec_p)
    assert bool(jnp.all(got == want))


def test_fused_capped_matches_at_float_tolerance():
    """The optional tanh soft-clip: XLA's tanh lowering is not
    bitwise-stable across fusion contexts (documented in bcsr_attn), so
    capped attention pins at tight float tolerance instead."""
    q, k, v = _qkv(64, 8)
    spec_f, spec_c = _specs(MASKS["local_global"])
    got = A.block_sparse_attention(q, k, v, spec_f, cap=30.0)
    want = A.block_sparse_attention(q, k, v, spec_c, cap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


def test_fused_empty_block_row_zero_context():
    """A block-row whose schedule holds only sentinel slots (no stored
    blocks at all) must produce exactly-zero context — the fused analogue
    of the composed path's clamped empty-row softmax."""
    L, d, h = 8, 4, 4
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, L, d)), jnp.float32)
               for _ in range(3))
    # row 0 stores block (0,0) fully unmasked; row 1 stores NOTHING
    emask = np.ones((1, h, h), np.float32)
    flat_idx = np.array([0, 1], np.int32)     # row 1 -> sentinel (nnzb=1)
    flat_col = np.array([0, 0], np.int32)
    out = bcsr_attn.bcsr_attn_fused(
        q, k, v, emask, flat_idx, flat_col, n_block_rows=2, n_block_cols=2,
        block=(h, h), scale=0.5, interpret=True)
    s = (q[0, :h] @ k[0, :h].T) * 0.5
    p = jax.nn.softmax(s, axis=-1)
    np.testing.assert_allclose(np.asarray(out[0, :h]),
                               np.asarray(p @ v[0, :h]), atol=1e-5)
    assert bool(jnp.all(out[0, h:] == 0.0))


# ========================================================== gradient parity
@pytest.mark.parametrize("mask_kind", ["banded", "blockwise_causal"])
def test_fused_gradients_match_composed(mask_kind):
    """Backward rides the composed dual-VJP route, so grads through the
    fused op must match differentiating the composed path directly."""
    q, k, v = _qkv(64, 8, seed=3)
    spec_f, spec_c = _specs(MASKS[mask_kind])

    def loss(spec):
        return lambda q, k, v: jnp.sum(
            A.block_sparse_attention(q, k, v, spec) ** 2)

    gf = jax.grad(loss(spec_f), argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(loss(spec_c), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(a).sum()) > 0


# ================================================= v7 fingerprints + dispatch
def test_v6_attn_key_pinned_and_never_aliases():
    """The v7 ``op=attn`` key layout is a cross-process cache contract,
    and fused/composed picks live in a key space disjoint from the
    composed path's sddmm/spmm picks over the SAME structure."""
    fp = autotune.Fingerprint(
        n_block_rows=4, n_block_cols=5, block=(16, 16), nnzb=10,
        pad_bucket=1, skew_bucket=2, n_bucket=64, reorder="jaccard",
        n_shards=2, max_bpr=3, op="attn")
    assert fp.key() == ("v7|op=attn|nbr=4|nbc=5|b=16x16|nnzb=10|pad=1"
                        "|skew=2|n=64|ro=jaccard|ns=2|mb=3|nk=1")

    meta = A.attention_mask_meta(A.banded(24), 64, (8, 8))
    keys = {op: autotune.fingerprint(meta, 8, op=op).key()
            for op in ("attn", "sddmm", "spmm")}
    assert len(set(keys.values())) == 3
    assert keys["attn"].startswith("v7|op=attn|")
    # a cached attn pick is invisible to the composed families
    tuner = autotune.get_autotuner()
    tuner.put(autotune.fingerprint(meta, 8, op="attn"),
              autotune.KernelChoice("attn_fused", 512), persist=False)
    assert tuner.get(autotune.fingerprint(meta, 8, op="sddmm")) is None
    assert tuner.get(autotune.fingerprint(meta, 8, op="spmm")) is None


def test_attn_family_registered_and_defaults_composed():
    assert set(autotune.variant_names("attn")) == {"attn_fused",
                                                   "attn_composed"}
    assert autotune.default_variant("attn") == "attn_composed"
    meta = A.attention_mask_meta(A.banded(24), 64, (8, 8))
    pick = autotune.get_autotuner().pick(meta, 8, op="attn")
    assert pick.variant in autotune.variant_names("attn")


def test_auto_backend_selects_fused_and_matches():
    """``backend="auto"`` must surface the fused kernel through the
    ``op=attn`` pick for a typical banded mask (the analytic model: one
    launch + no probs traffic beats three launches), and the result must
    still equal the composed reference bitwise."""
    mask = A.banded(24)
    spec_a = A.AttnSparsitySpec(mask=mask, block=(8, 8), backend="auto",
                                interpret=True)
    assert A.resolve_attn_impl(spec_a, 64, 8) == "fused"
    q, k, v = _qkv(64, 8)
    _, spec_c = _specs(mask)
    got = A.block_sparse_attention(q, k, v, spec_a)
    want = A.block_sparse_attention(q, k, v, spec_c)
    assert bool(jnp.all(got == want))


def test_explicit_and_sharded_specs_stay_composed():
    mask = A.banded(24)
    for backend in ("xla", "pallas", "dense"):
        spec = A.AttnSparsitySpec(mask=mask, block=(8, 8), backend=backend)
        assert A.resolve_attn_impl(spec, 64, 8) == "composed"
    sharded = A.AttnSparsitySpec(mask=mask, block=(8, 8), backend="fused",
                                 interpret=True, shards=2)
    assert A.resolve_attn_impl(sharded, 64, 8) == "composed"
    # ...and the sharded composed fallback still agrees with the
    # unsharded composed math (backend "fused" normalized to "auto")
    q, k, v = _qkv(64, 8)
    got = A.block_sparse_attention(q, k, v, sharded)
    want = A.block_sparse_attention(q, k, v, _specs(mask)[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_under_jit_and_report_fields():
    """The fused dispatch is trace-safe (static info only) and the
    dry-run report carries the attention-level resolution."""
    mask = A.banded(24)
    spec = A.AttnSparsitySpec(mask=mask, block=(8, 8), backend="auto",
                              interpret=True)
    q, k, v = _qkv(64, 8)
    out = jax.jit(lambda q, k, v: A.block_sparse_attention(q, k, v, spec))(
        q, k, v)
    ref = A.block_sparse_attention(q, k, v, _specs(mask)[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    rep = A.attention_mask_report(spec, 64, head_dim=8)
    assert rep["attn_impl"] == "fused"
    assert rep["attn_pick"] in autotune.variant_names("attn")
    # explicit kernel backends report the composed resolution
    rep_x = A.attention_mask_report(
        dataclasses.replace(spec, backend="xla"), 64, head_dim=8)
    assert rep_x["attn_impl"] == "composed"


def test_fused_schedule_matches_ops_row_loop_schedule():
    """The host schedule the fused path memoizes passes the launch
    verifier's schedule contract: every stored block in exactly one live
    slot of its block-row, the sentinel entry only on padding."""
    from repro.analysis import verify_launch as vl
    arrays, meta = A.attention_mask_arrays(A.local_global(16, 8), 61, (8, 8))
    emask, flat_idx, flat_col, meta2 = A._fused_inputs(
        A.local_global(16, 8), 61, (8, 8))
    assert meta2 == meta
    assert vl.verify_schedule("attn", flat_idx, flat_col, arrays.row_ids,
                              arrays.col_ids, meta) == []
    assert emask.shape == arrays.vals.shape
