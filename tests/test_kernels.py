"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes, block sizes, densities and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcsr as bcsr_lib
from repro.kernels import bcsr_spmm as pk
from repro.kernels import ops, ref


def _mk(shape, block, density, seed=0, dtype=np.float32, fill=1.0):
    a = bcsr_lib.random_bcsr(seed, shape, block, density, dtype=dtype,
                             fill_density=fill)
    return a.ensure_nonempty_rows()


SHAPES = [
    ((64, 64), (8, 8), 0.5),
    ((128, 256), (16, 32), 0.3),
    ((256, 128), (32, 16), 0.15),
    ((96, 160), (16, 16), 0.4),
]

# the nnz-stream kernel's row panels (R * h = 128 rows of C): 13 block-rows
# of 16 make panels of 8 and 5; blocks of 128 rows make one block-row each
PANEL_SHAPES = [
    ((208, 128), (16, 16), 0.3),
    ((384, 256), (128, 128), 0.5),
]


@pytest.mark.parametrize("shape,block,density", SHAPES + PANEL_SHAPES)
@pytest.mark.parametrize("n", [8, 64])
def test_nnz_stream_matches_ref(shape, block, density, n):
    # bn = 32 < N = 64: two N tiles, and the copies run on from one into
    # the next
    a = _mk(shape, block, density)
    b = np.random.default_rng(1).standard_normal(
        (shape[1], n)).astype(np.float32)
    got = pk.bcsr_spmm_nnz_stream(
        jnp.asarray(a.vals), jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
        jnp.asarray(b), a.n_block_rows, bn=min(32, n), interpret=True)
    want = ref.bcsr_spmm_ref(
        jnp.asarray(a.vals), jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
        jnp.asarray(b), a.n_block_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,block,density", SHAPES[:2])
def test_nnz_stream_matches_dense(shape, block, density):
    a = _mk(shape, block, density, fill=0.6)
    b = np.random.default_rng(2).standard_normal(
        (shape[1], 32)).astype(np.float32)
    got = pk.bcsr_spmm_nnz_stream(
        jnp.asarray(a.vals), jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
        jnp.asarray(b), a.n_block_rows, bn=32, interpret=True)
    want = a.to_dense() @ b
    np.testing.assert_allclose(np.asarray(got)[: shape[0]], want,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "dtype,block", [(np.float32, (16, 16)), (jnp.bfloat16, (16, 16)),
                    (np.float32, (128, 128)), (jnp.bfloat16, (128, 128))],
    ids=["float32", "bfloat16", "float32-h128", "bfloat16-h128"])
def test_nnz_stream_dtypes(dtype, block):
    shape = (256, 256)          # h = 16: two panels; h = 128: two block-rows
    a = _mk(shape, block, 0.3, dtype=np.float32)
    vals = jnp.asarray(a.vals).astype(dtype)
    b = jnp.asarray(np.random.default_rng(3).standard_normal(
        (256, 64)).astype(np.float32)).astype(dtype)
    got = pk.bcsr_spmm_nnz_stream(
        vals, jnp.asarray(a.row_ids), jnp.asarray(a.col_ids), b,
        a.n_block_rows, bn=64, interpret=True)
    want = ref.bcsr_spmm_ref(vals, jnp.asarray(a.row_ids),
                             jnp.asarray(a.col_ids), b, a.n_block_rows)
    assert got.dtype == b.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2)


def _panel_edge_case(name: str):
    """Structures at the edges of the nnz-stream kernel's panel schedule."""
    rng = np.random.default_rng(16)
    if name == "one_block_per_row":
        # 16 block-rows (two panels) of one block each, columns scattered
        dense = np.zeros((256, 128), np.float32)
        for i in range(16):
            c = (5 * i) % 8
            dense[16 * i:16 * i + 16, 16 * c:16 * c + 16] = \
                rng.standard_normal((16, 16))
        return bcsr_lib.from_dense(dense, (16, 16))
    if name == "row_longer_than_ring":
        # block-row 1 holds 32 blocks, twice the ring's 16 slots
        dense = np.zeros((48, 512), np.float32)
        dense[16:32] = rng.standard_normal((16, 512))
        dense[3, 40] = 1.0
        dense[40, 500] = -2.0
        return bcsr_lib.from_dense(dense, (16, 16))
    raise ValueError(name)


@pytest.mark.parametrize("name", ["one_block_per_row",
                                  "row_longer_than_ring"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_nnz_stream_panel_edges(name, dtype):
    """Checked under the TPU interpreter, which runs each DMA only when it
    is waited for and fills uninitialised VMEM with NaN: a block read from
    its ring slot before its copy landed, or overwritten by the next copy,
    shows in the result."""
    from jax.experimental.pallas import tpu as pltpu
    a = _panel_edge_case(name)
    vals = jnp.asarray(a.vals).astype(dtype)
    b = jnp.asarray(np.random.default_rng(17).standard_normal(
        (a.shape[1], 64)).astype(np.float32)).astype(dtype)
    ids = jnp.asarray(a.row_ids), jnp.asarray(a.col_ids)
    got = pk.bcsr_spmm_nnz_stream(
        vals, *ids, b, a.n_block_rows, bn=32,
        interpret=pltpu.InterpretParams(dma_execution_mode="on_wait",
                                        uninitialized_memory="nan"))
    want = ref.bcsr_spmm_ref(vals, *ids, b, a.n_block_rows)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_nnz_stream_blocks_per_step_gauge():
    from repro.obs import metrics
    a = _mk(*PANEL_SHAPES[0])          # 13 block-rows: 2 panels of R = 8
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    b = jnp.ones((a.shape[1], 8), jnp.float32)
    ops.spmm(arrays, meta, b, backend="pallas", bn=128, interpret=True)
    gauges = metrics.snapshot()["gauges"]
    assert gauges["kernel.spmm.blocks_per_step{op=spmm}"] == meta.nnzb / 2


@pytest.mark.parametrize("shape,block,density", SHAPES[:3])
def test_row_loop_matches_ref(shape, block, density):
    a = _mk(shape, block, density)
    b = np.random.default_rng(4).standard_normal(
        (shape[1], 32)).astype(np.float32)
    flat_idx, flat_col, row_len, max_bpr = ops.make_row_loop_schedule(a)
    got = pk.bcsr_spmm_row_loop(
        jnp.asarray(a.vals), flat_idx, flat_col, row_len,
        jnp.asarray(b), a.n_block_rows, bn=32, interpret=True)
    want = ref.bcsr_spmm_ref(
        jnp.asarray(a.vals), jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
        jnp.asarray(b), a.n_block_rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_row_loop_handles_empty_and_skewed_rows():
    # adversarial: rows with 0 blocks and one row with many (the dc2 case)
    rng = np.random.default_rng(5)
    dense = np.zeros((64, 128), np.float32)
    dense[3, :] = rng.standard_normal(128)      # very dense row
    dense[17, 5] = 1.0                           # singleton
    a = bcsr_lib.from_dense(dense, (8, 16))
    b = rng.standard_normal((128, 16)).astype(np.float32)
    flat_idx, flat_col, row_len, _ = ops.make_row_loop_schedule(a)
    got = pk.bcsr_spmm_row_loop(
        jnp.asarray(a.vals), flat_idx, flat_col, row_len, jnp.asarray(b),
        a.n_block_rows, bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), dense @ b, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("shape,block,density", SHAPES[:3])
def test_sddmm_matches_ref(shape, block, density):
    a = _mk(shape, block, density)
    h, w = block
    rng = np.random.default_rng(6)
    M = a.n_block_rows * h
    dc = rng.standard_normal((M, 32)).astype(np.float32)
    b = rng.standard_normal((a.n_block_cols * w, 32)).astype(np.float32)
    got = pk.bcsr_sddmm(jnp.asarray(dc), jnp.asarray(b),
                        jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
                        h, w, bn=32, interpret=True)
    want = ref.bcsr_sddmm_ref(jnp.asarray(dc), jnp.asarray(b),
                              jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
                              h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ ops level
@pytest.mark.parametrize("backend", ["pallas", "xla", "dense"])
def test_ops_spmm_forward(backend):
    shape, block = (96, 128), (16, 16)
    a = _mk(shape, block, 0.3)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    b = jnp.asarray(np.random.default_rng(7).standard_normal(
        (shape[1], 40)).astype(np.float32))
    got = ops.spmm(arrays, meta, b, backend=backend, bn=128, interpret=True)
    want = a.to_dense() @ np.asarray(b)
    assert got.shape == (shape[0], 40)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "backend,block", [("pallas", (16, 16)), ("xla", (16, 16)),
                      ("pallas", (16, 128))],
    ids=["pallas", "xla", "pallas-16x128"])
def test_ops_spmm_grads(backend, block):
    # at (16, 128) the backward streams the transpose's 128 x 16 blocks
    shape = (64, 96) if block == (16, 16) else (96, 384)
    a = _mk(shape, block, 0.4)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    rng = np.random.default_rng(8)
    b = jnp.asarray(rng.standard_normal((shape[1], 24)).astype(np.float32))

    def loss(vals, b):
        arr = arrays._replace(vals=vals)
        out = ops.spmm(arr, meta, b, backend=backend, bn=128, interpret=True)
        return jnp.sum(out * out)

    g_vals, g_b = jax.grad(loss, argnums=(0, 1))(arrays.vals, b)

    # numeric oracle via the dense equivalent
    def loss_dense(vals, b):
        arr = arrays._replace(vals=vals)
        dense = ops.materialize_dense(arr, meta)[: shape[0], : shape[1]]
        out = dense @ b
        return jnp.sum(out * out)

    g_vals_d, g_b_d = jax.grad(loss_dense, argnums=(0, 1))(arrays.vals, b)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_b_d),
                               rtol=1e-3, atol=1e-3)
    mask = np.asarray(arrays.real_mask)[:, None, None]
    np.testing.assert_allclose(np.asarray(g_vals),
                               np.asarray(g_vals_d) * mask,
                               rtol=1e-3, atol=1e-3)


def test_ops_unaligned_shapes():
    # M, K, N not multiples of the block/tile — wrapper pads & slices
    dense = np.random.default_rng(9).standard_normal((50, 70)).astype(
        np.float32)
    dense[np.abs(dense) < 1.0] = 0
    a = bcsr_lib.from_dense(dense, (16, 16))
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    b = jnp.asarray(np.random.default_rng(10).standard_normal(
        (70, 33)).astype(np.float32))
    got = ops.spmm(arrays, meta, b, backend="pallas", bn=128,
                   interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:50], dense @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)


# ===================================================================== SDDMM
def _sddmm_oracle(a, dc, b):
    """Dense masked-einsum oracle: blocks of dC @ B^T at the stored
    coordinates (f32 accumulation)."""
    h, w = a.block
    full = np.asarray(dc, np.float32) @ np.asarray(b, np.float32).T
    nbr, nbc = full.shape[0] // h, full.shape[1] // w
    blocks = full.reshape(nbr, h, nbc, w).transpose(0, 2, 1, 3)
    return blocks[np.asarray(a.row_ids), np.asarray(a.col_ids)]


@pytest.mark.parametrize("shape,block,density", SHAPES)
@pytest.mark.parametrize("n", [8, 64])
def test_sddmm_matches_dense_masked_einsum(shape, block, density, n):
    a = _mk(shape, block, density)
    rng = np.random.default_rng(11)
    h, w = block
    M = a.n_block_rows * h
    K = a.n_block_cols * w
    dc = rng.standard_normal((M, n)).astype(np.float32)
    b = rng.standard_normal((K, n)).astype(np.float32)
    want = _sddmm_oracle(a, dc, b)
    got = pk.bcsr_sddmm(jnp.asarray(dc), jnp.asarray(b),
                        jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
                        h, w, bn=min(64, n), interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    got_ref = ref.bcsr_sddmm_ref(jnp.asarray(dc), jnp.asarray(b),
                                 jnp.asarray(a.row_ids),
                                 jnp.asarray(a.col_ids), h, w)
    np.testing.assert_allclose(np.asarray(got_ref), want,
                               rtol=1e-5, atol=1e-5)
    got_dense = ref.bcsr_sddmm_dense_ref(jnp.asarray(dc), jnp.asarray(b),
                                         jnp.asarray(a.row_ids),
                                         jnp.asarray(a.col_ids), h, w)
    np.testing.assert_allclose(np.asarray(got_dense), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,block,density", SHAPES[:3])
def test_sddmm_row_loop_matches_ref(shape, block, density):
    a = _mk(shape, block, density)
    rng = np.random.default_rng(12)
    h, w = block
    dc = rng.standard_normal((a.n_block_rows * h, 32)).astype(np.float32)
    b = rng.standard_normal((a.n_block_cols * w, 32)).astype(np.float32)
    flat_idx, flat_col, _, max_bpr = ops.make_row_loop_schedule(a)
    # sddmm schedule: padding slots must point at the SENTINEL entry, not 0
    sched_idx, sched_col = ops._sddmm_row_loop_schedule(
        jnp.asarray(a.row_ids), jnp.asarray(a.col_ids), a.n_block_rows,
        max_bpr)
    got = pk.bcsr_sddmm_row_loop(
        jnp.asarray(dc), jnp.asarray(b), sched_idx, sched_col,
        a.n_block_rows, a.nnzb, h, w, bn=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), _sddmm_oracle(a, dc, b),
                               rtol=1e-5, atol=1e-5)


def test_sddmm_row_loop_skewed_and_empty_rows():
    # dc2-style skew + empty block-rows: sentinel slots must not clobber
    # entry 0 (the regression the sentinel output block exists for)
    rng = np.random.default_rng(13)
    dense = np.zeros((64, 128), np.float32)
    dense[3, :] = rng.standard_normal(128)       # one very dense row
    dense[17, 5] = 1.0                           # singleton
    a = bcsr_lib.from_dense(dense, (8, 16)).ensure_nonempty_rows()
    dc = rng.standard_normal((64, 16)).astype(np.float32)
    b = rng.standard_normal((128, 16)).astype(np.float32)
    _, _, _, max_bpr = ops.make_row_loop_schedule(a)
    sched_idx, sched_col = ops._sddmm_row_loop_schedule(
        jnp.asarray(a.row_ids), jnp.asarray(a.col_ids), a.n_block_rows,
        max_bpr)
    got = pk.bcsr_sddmm_row_loop(
        jnp.asarray(dc), jnp.asarray(b), sched_idx, sched_col,
        a.n_block_rows, a.nnzb, 8, 16, bn=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), _sddmm_oracle(a, dc, b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_sddmm_dtypes_f32_accumulation(dtype):
    # mixed-precision contract: inputs may be bf16, accumulation is f32
    # VMEM scratch, output takes the requested dtype
    shape, block = (128, 128), (16, 16)
    a = _mk(shape, block, 0.3)
    rng = np.random.default_rng(14)
    dc = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32)
                     ).astype(dtype)
    b = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32)
                    ).astype(dtype)
    got = pk.bcsr_sddmm(dc, b, jnp.asarray(a.row_ids),
                        jnp.asarray(a.col_ids), 16, 16, bn=64,
                        out_dtype=jnp.float32, interpret=True)
    assert got.dtype == jnp.float32
    want = _sddmm_oracle(a, np.asarray(dc, np.float32),
                         np.asarray(b, np.float32))
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def test_ops_sddmm_ragged_and_empty_rows():
    # M, K not multiples of the block; genuinely empty block-rows whose
    # padding entries must come back exactly zero (real_mask)
    rng = np.random.default_rng(15)
    dense = np.zeros((50, 70), np.float32)
    dense[0:8, 0:16] = rng.standard_normal((8, 16))
    dense[33:41, 48:64] = rng.standard_normal((8, 16))
    a = bcsr_lib.from_dense(dense, (8, 16))
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((50, 24)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((70, 24)).astype(np.float32))
    x_pad = np.zeros((meta.n_block_rows * 8, 24), np.float32)
    x_pad[:50] = np.asarray(x)
    y_pad = np.zeros((meta.n_block_cols * 16, 24), np.float32)
    y_pad[:70] = np.asarray(y)
    h, w = meta.block
    full = x_pad @ y_pad.T
    blocks = full.reshape(meta.n_block_rows, h, meta.n_block_cols, w
                          ).transpose(0, 2, 1, 3)
    want = blocks[np.asarray(arrays.row_ids), np.asarray(arrays.col_ids)]
    want *= np.asarray(arrays.real_mask)[:, None, None]
    for backend in ("pallas", "row_loop", "xla", "dense"):
        got = ops.sddmm(arrays, meta, x, y, backend=backend, bn=64,
                        interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4, err_msg=backend)
        pad_rows = ~np.asarray(arrays.real_mask)
        assert pad_rows.any()            # the case genuinely has padding
        assert np.all(np.asarray(got)[pad_rows] == 0.0)
