"""Property-based differential tests for the sharded, overlap-chunked
SpMM (``launch.dist_spmm``) against the single-device ``ops.spmm``.

The generator draws random block structures — density, row skew, ragged
(non-multiple-of-block) tails, empty block-rows, rectangular dims — and
for every shard count S in {1, 2, 4, 8} x chunk depth in {1, 2, 4} x
backend asserts the differential contracts:

  * forward: ``spmm_sharded`` is BIT-identical (uint32 view) to the
    unsharded ``ops.spmm`` under the SAME backend — sharding assigns each
    output block-row to exactly one shard and the chunked pipeline
    concatenates disjoint column panels, so no summation order changes;
  * VJP: dvals is bit-identical to the unsharded reference on the real
    support (the value grads flow through the same per-entry contraction;
    the chunked path differentiates via the unchunked exec), and dB
    matches to fp32 tolerance (cross-shard scatter-add order differs).

The explicit regression corpus at the bottom pins the structures that
historically carried the edge cases (ragged tails, empty shards, skew,
pre-reorder composition).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
import scipy.sparse as sp
from hypothesis import example, given, settings, strategies as st

from repro.core import bcsr as bcsr_lib
from repro.core import topology
from repro.kernels import ops
from repro.launch import dist_spmm

SHARD_COUNTS = (1, 2, 4, 8)
CHUNK_COUNTS = (1, 2, 4)
BLOCK = (16, 16)


# ------------------------------------------------------------- generators
def _random_structure(kind: str, nbr: int, nbc: int, tail_r: int,
                      tail_c: int, density: float, seed: int):
    """A BCSR matrix with the requested pathology.

    ``kind``:
      * ``uniform``    — iid Bernoulli support at ``density``;
      * ``skewed``     — per-row densities follow a power law (a few rows
                         carry most of the support; extreme single-row skew);
      * ``empty_rows`` — uniform support with ~1/3 of the BLOCK-rows
                         zeroed out entirely (empty shards downstream).
    """
    m = nbr * BLOCK[0] - tail_r
    k = nbc * BLOCK[1] - tail_c
    rng = np.random.default_rng(seed)
    if kind == "skewed":
        w = (1.0 / (1.0 + np.arange(m)) ** 0.8)
        p_row = np.minimum(density * m * w / w.sum() * 3.0, 0.9)
    else:
        p_row = np.full(m, density)
    if kind == "empty_rows":
        dead = rng.permutation(nbr)[:max(nbr // 3, 1)]
        for br in dead:
            p_row[br * BLOCK[0]:(br + 1) * BLOCK[0]] = 0.0
    mask = rng.random((m, k)) < p_row[:, None]
    dense = np.where(mask, rng.standard_normal((m, k)), 0.0)
    return bcsr_lib.from_scipy(sp.csr_matrix(dense.astype(np.float32)),
                               BLOCK)


def _prepare(a, n_shards):
    """``(sharr, smeta, split)``: ``prepare_sharded`` at ``n_shards``.  A
    structure that gets the documented refusal (one block-row heavier than
    a balanced shard would pad every shard to its size) is prepared again
    with ``split_heavy_rows=True`` and ``split`` is True: its heavy rows'
    partial sums then meet in a scatter-add, so results hold to tolerance,
    not bit for bit."""
    try:
        return (*dist_spmm.prepare_sharded(a, n_shards, dtype=jnp.float32),
                False)
    except ValueError as e:
        assert "split_heavy_rows=True" in str(e), e
        assert int(np.diff(a.rowptr).max()) > -(-a.nnzb // n_shards)
    sharr, smeta = dist_spmm.prepare_sharded(a, n_shards, dtype=jnp.float32,
                                             split_heavy_rows=True)
    assert smeta.n_split_fragments > 0
    return sharr, smeta, True


def _b_for(a, n=24, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((a.shape[1], n)).astype(np.float32))


def _assert_matches(out, ref, split, msg):
    """Bit-identical, or to fp32 tolerance for a split-row partition."""
    if split:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=msg)
    else:
        _assert_bitwise(out, ref, msg)


def _assert_bitwise(out, ref, msg):
    got = np.asarray(out)
    want = np.asarray(ref)
    assert got.shape == want.shape, msg
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32)), \
        f"{msg}: not bit-identical (max abs diff " \
        f"{np.abs(got - want).max()})"


# -------------------------------------------------------- forward property
@settings(max_examples=5, deadline=None)
# a heavy single block-row that plain prepare_sharded refuses at S=8:
# pinned so the split-row fallback runs in every session
@example(kind="skewed", nbr=2, nbc=7, tail_r=0, tail_c=0, density=0.2,
         seed=0)
@given(kind=st.sampled_from(["uniform", "skewed", "empty_rows"]),
       nbr=st.integers(2, 7), nbc=st.integers(2, 7),
       tail_r=st.sampled_from([0, 0, 5, 11]),
       tail_c=st.sampled_from([0, 0, 3]),
       density=st.floats(0.08, 0.5),
       seed=st.integers(0, 10_000))
def test_forward_bitwise_property(kind, nbr, nbc, tail_r, tail_c,
                                  density, seed):
    """Every (S, n_chunks, backend) produces the same bits as the
    unsharded same-backend reference."""
    a = _random_structure(kind, nbr, nbc, tail_r, tail_c, density, seed)
    if a.nnzb == 0:
        return  # degenerate draw: nothing to multiply
    b = _b_for(a)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    tag = f"{kind} nbr={nbr} nbc={nbc} tails=({tail_r},{tail_c}) " \
          f"d={density:.2f} seed={seed}"
    for backend in ("xla", "pallas"):
        ref = ops.spmm(arrays, meta, b, backend=backend, interpret=True)
        # pallas-interpret is slow: spot-check (S, chunks) there, sweep
        # the full grid on xla (the corpus covers pallas chunk depths)
        shard_counts = SHARD_COUNTS if backend == "xla" else (1, 4)
        for n_shards in shard_counts:
            sharr, smeta, split = _prepare(a, n_shards)
            chunks = CHUNK_COUNTS if backend == "xla" else (1, 4)
            for k in chunks:
                out = dist_spmm.spmm_sharded(sharr, smeta, b,
                                             backend=backend, n_chunks=k,
                                             interpret=True)
                _assert_matches(out, ref, split, f"{tag} {backend} "
                                f"S={n_shards} nk={k} split={split}")


# ------------------------------------------------------------ VJP property
@settings(max_examples=4, deadline=None)
@given(kind=st.sampled_from(["uniform", "skewed", "empty_rows"]),
       nbr=st.integers(2, 6), nbc=st.integers(2, 6),
       tail_r=st.sampled_from([0, 7]),
       density=st.floats(0.1, 0.4),
       seed=st.integers(0, 10_000))
def test_vjp_property(kind, nbr, nbc, tail_r, density, seed):
    """dvals bit-identical to the unsharded reference on the real support;
    dB within fp32 tolerance — at every shard count and chunk depth."""
    a = _random_structure(kind, nbr, nbc, tail_r, 0, density, seed)
    if a.nnzb == 0:
        return
    b = _b_for(a, n=20)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    tag = f"{kind} nbr={nbr} nbc={nbc} tail={tail_r} seed={seed}"

    def loss_ref(v, bb):
        arr = ops.SparseArrays(v, *arrays[1:])
        return jnp.sum(ops.spmm(arr, meta, bb, backend="xla") ** 2)

    rv, rb = jax.grad(loss_ref, argnums=(0, 1))(arrays.vals, b)
    for n_shards in SHARD_COUNTS:
        sharr, smeta, split = _prepare(a, n_shards)
        for k in CHUNK_COUNTS:
            def loss_sh(v, bb, _k=k, _sh=sharr, _sm=smeta):
                out = dist_spmm.spmm_sharded(_sh._replace(vals=v), _sm,
                                             bb, backend="xla",
                                             n_chunks=_k)
                return jnp.sum(out ** 2)

            gv, gb = jax.grad(loss_sh, argnums=(0, 1))(sharr.vals, b)
            if split:   # a split row's dvals see the scatter-added output
                np.testing.assert_allclose(
                    np.asarray(gv), np.asarray(rv), rtol=1e-4, atol=1e-3,
                    err_msg=f"{tag} S={n_shards} nk={k} dvals split")
            else:
                _assert_bitwise(gv, rv, f"{tag} S={n_shards} nk={k} dvals")
            np.testing.assert_allclose(
                np.asarray(gb), np.asarray(rb), rtol=1e-4, atol=1e-3,
                err_msg=f"{tag} S={n_shards} nk={k} dB")


# -------------------------------------------------------- regression corpus
def _corpus():
    """Explicit structures that carried historical edge cases."""
    return [
        ("ragged_partial",
         bcsr_lib.random_bcsr(0, (23 * 16 + 5, 160), BLOCK, 0.3)),
        ("power_law_skew",
         bcsr_lib.from_scipy(topology.power_law(500, 5.0, seed=2), BLOCK)),
        ("rect_wide",
         bcsr_lib.random_bcsr(3, (96, 400), BLOCK, 0.2)),
        ("empty_block_rows",
         _random_structure("empty_rows", 6, 5, 0, 0, 0.3, 9)),
        ("tiny_fewer_rows_than_shards",
         bcsr_lib.random_bcsr(1, (30, 64), BLOCK, 0.5)),
    ]


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("n_chunks", CHUNK_COUNTS)
def test_corpus_forward_bitwise(n_shards, n_chunks):
    for name, a in _corpus():
        b = _b_for(a)
        arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
        ref = ops.spmm(arrays, meta, b, backend="xla")
        sharr, smeta = dist_spmm.prepare_sharded(a, n_shards,
                                                 dtype=jnp.float32)
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla",
                                     n_chunks=n_chunks)
        _assert_bitwise(out, ref, f"{name} S={n_shards} nk={n_chunks}")


@pytest.mark.parametrize("n_chunks", CHUNK_COUNTS)
def test_corpus_forward_bitwise_pallas(n_chunks):
    """The kernel backend agrees with itself under sharding + chunking."""
    for name, a in _corpus()[:2]:
        b = _b_for(a, n=16)
        arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
        ref = ops.spmm(arrays, meta, b, backend="pallas", interpret=True)
        sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="pallas",
                                     n_chunks=n_chunks, interpret=True)
        _assert_bitwise(out, ref, f"{name} pallas nk={n_chunks}")


def test_corpus_chunked_jit_matches_eager():
    """jit tracing the chunked dispatch changes nothing (the schedule is
    static python — same XLA program either way)."""
    _, a = _corpus()[0]
    b = _b_for(a)
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    eager = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla",
                                   n_chunks=4)
    jitted = jax.jit(lambda bb: dist_spmm.spmm_sharded(
        sharr, smeta, bb, backend="xla", n_chunks=4))(b)
    _assert_bitwise(jitted, eager, "jit vs eager nk=4")


def test_corpus_reorder_composes_with_chunking():
    """Pre-reorder + sharding + chunking still returns the ORIGINAL row
    order (allclose — the permutation changes accumulation order)."""
    a = bcsr_lib.from_scipy(
        topology.blocked_random(n=512, nnz_target=9000, cluster=16, seed=1),
        BLOCK)
    b = _b_for(a)
    arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    ref = ops.spmm(arrays, meta, b, backend="xla")
    sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32,
                                             reorder="jaccard")
    for k in CHUNK_COUNTS:
        out = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla",
                                     n_chunks=k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4,
                                   err_msg=f"jaccard nk={k}")
