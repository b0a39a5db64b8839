"""Pallas TPU kernels for BCSR SpMM — the paper's contribution, MXU-native.

Four kernels:

  * ``bcsr_spmm_nnz_stream``  — production forward. The grid streams the
    *nonzero-block list* (beyond-paper: zero pipeline bubbles regardless of
    row skew — this removes SMaT's ``dc2`` worst case).  The BCSR index
    arrays are scalar-prefetched into SMEM and drive data-dependent
    HBM->VMEM DMA through the BlockSpec ``index_map`` — the TPU-idiomatic
    replacement for SMaT's ``ldmatrix`` + ``cuda::memcpy_async`` pipeline
    (Pallas double-buffers the DMA against the MXU automatically).

  * ``bcsr_spmm_row_loop``    — the paper-faithful *static schedule*: one
    output tile per (block-row x N-tile) grid cell, looping to
    ``max_blocks_per_row`` with masking, exactly like SMaT's warp-per-C-tile
    2D schedule (wasted iterations on short rows; used as the faithful
    baseline in benchmarks).

  * ``bcsr_sddmm``            — block-sampled dense-dense product
    (``X @ Y^T`` evaluated only at the stored blocks), streamed over the
    nonzero-block list.  It is both the backward pass of SpMM (dW of a
    sparse weight) and, since PR 5, the forward of the public
    ``ops.sddmm`` — the score kernel of block-sparse attention.

  * ``bcsr_sddmm_row_loop``   — the paper-faithful static-schedule SDDMM
    twin: one grid cell per (block-row x slot x N-tile), looping to
    ``max_blocks_per_row``; padding slots write into a sentinel output
    block (SMaT's static waste, mirrored from the SpMM ``row_loop``).

Blocks are ``(h, w)`` with ``h`` a sublane multiple (8 f32 / 16 bf16) and
``w`` a lane multiple (128) on real TPUs; ``interpret=True`` (CPU CI) accepts
any shape.  All kernels accumulate in f32 VMEM scratch regardless of input
dtype (MXU-native mixed precision; the paper uses fp16-in/fp16-out on TC —
documented deviation, see docs/ARCHITECTURE.md "Mixed-precision contract").
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# =============================================================== nnz-streamed
def _nnz_stream_kernel(row_ref, col_ref, vals_ref, b_ref, o_ref, acc_ref,
                       *, nnzb: int):
    s = pl.program_id(1)
    row = row_ref[s]
    prev_row = row_ref[jnp.maximum(s - 1, 0)]
    next_row = row_ref[jnp.minimum(s + 1, nnzb - 1)]
    is_first = jnp.logical_or(s == 0, prev_row != row)
    is_last = jnp.logical_or(s == nnzb - 1, next_row != row)

    @pl.when(is_first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        vals_ref[0], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(is_last)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bcsr_spmm_nnz_stream(vals: jnp.ndarray, row_ids: jnp.ndarray,
                         col_ids: jnp.ndarray, b: jnp.ndarray,
                         n_block_rows: int, *, bn: int = 512,
                         out_dtype=None, interpret: bool = False):
    """C[nbr*h, N] = A_bcsr @ B.  Entries must be sorted row-major and every
    block-row must contain >= 1 entry (``BCSR.ensure_nonempty_rows``)."""
    nnzb, h, w = vals.shape
    K, N = b.shape
    assert K % w == 0, (K, w)
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    out_dtype = out_dtype or b.dtype
    grid = (N // bn, nnzb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # A block s: data-dependent DMA driven by the prefetched ids
            pl.BlockSpec((1, h, w), lambda j, s, row_ref, col_ref: (s, 0, 0)),
            # B block (col_ids[s], j)
            pl.BlockSpec((w, bn),
                         lambda j, s, row_ref, col_ref: (col_ref[s], j)),
        ],
        out_specs=pl.BlockSpec(
            (h, bn), lambda j, s, row_ref, col_ref: (row_ref[s], j)),
        scratch_shapes=[pltpu.VMEM((h, bn), jnp.float32)],
    )
    kernel = functools.partial(_nnz_stream_kernel, nnzb=nnzb)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_block_rows * h, N), out_dtype),
        interpret=interpret,
        name="smat_spmm_nnz_stream",
    )(row_ids, col_ids, vals, b)


# ================================================================== row-loop
def _row_loop_kernel(idx_ref, col_ref, len_ref, vals_ref, b_ref, o_ref,
                     acc_ref, *, max_bpr: int):
    i = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < len_ref[i])
    def _mac():
        acc_ref[...] += jax.lax.dot(
            vals_ref[0], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(t == max_bpr - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bcsr_spmm_row_loop(vals: jnp.ndarray, flat_idx: jnp.ndarray,
                       flat_col: jnp.ndarray, row_len: jnp.ndarray,
                       b: jnp.ndarray, n_block_rows: int, *, bn: int = 512,
                       out_dtype=None, interpret: bool = False):
    """Paper-faithful static 2D schedule.

    flat_idx [nbr*max_bpr]  entry index per (row, slot); padding slots point
                            at entry 0 (their DMA still happens — faithful to
                            SMaT's static waste on short rows).
    flat_col [nbr*max_bpr]  block-col per (row, slot) (padding -> 0)
    row_len  [nbr]          nonzero blocks in each row
    """
    nnzb, h, w = vals.shape
    K, N = b.shape
    assert K % w == 0
    bn = min(bn, N)
    assert N % bn == 0
    out_dtype = out_dtype or b.dtype
    max_bpr = flat_idx.shape[0] // n_block_rows
    grid = (n_block_rows, N // bn, max_bpr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, w),
                         lambda i, j, t, idx_ref, col_ref, len_ref:
                         (idx_ref[i * max_bpr + t], 0, 0)),
            pl.BlockSpec((w, bn),
                         lambda i, j, t, idx_ref, col_ref, len_ref:
                         (col_ref[i * max_bpr + t], j)),
        ],
        out_specs=pl.BlockSpec(
            (h, bn), lambda i, j, t, idx_ref, col_ref, len_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((h, bn), jnp.float32)],
    )
    kernel = functools.partial(_row_loop_kernel, max_bpr=max_bpr)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_block_rows * h, N), out_dtype),
        interpret=interpret,
        name="smat_spmm_row_loop",
    )(flat_idx, flat_col, row_len, vals, b)


# ===================================================================== SDDMM
def _sddmm_kernel(row_ref, col_ref, dc_ref, b_ref, dv_ref, acc_ref,
                  *, n_tiles: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [h, bn] x [w, bn]^T -> [h, w]
    acc_ref[...] += jax.lax.dot_general(
        dc_ref[...], b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _flush():
        dv_ref[0] = acc_ref[...].astype(dv_ref.dtype)


def bcsr_sddmm(dc: jnp.ndarray, b: jnp.ndarray, row_ids: jnp.ndarray,
               col_ids: jnp.ndarray, h: int, w: int, *, bn: int = 512,
               out_dtype=None, interpret: bool = False):
    """dVals[s] = dC[block row_ids[s]] @ B[block col_ids[s]]^T — the sparse
    weight gradient, computed only at the stored blocks."""
    M, N = dc.shape
    K, _ = b.shape
    assert M % h == 0 and K % w == 0
    bn = min(bn, N)
    assert N % bn == 0
    nnzb = row_ids.shape[0]
    out_dtype = out_dtype or dc.dtype
    n_tiles = N // bn
    grid = (nnzb, n_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((h, bn),
                         lambda s, j, row_ref, col_ref: (row_ref[s], j)),
            pl.BlockSpec((w, bn),
                         lambda s, j, row_ref, col_ref: (col_ref[s], j)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, w), lambda s, j, row_ref, col_ref: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, w), jnp.float32)],
    )
    kernel = functools.partial(_sddmm_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nnzb, h, w), out_dtype),
        interpret=interpret,
        name="smat_sddmm_nnz_stream",
    )(row_ids, col_ids, dc, b)


# ========================================================== SDDMM (row-loop)
def _sddmm_row_loop_kernel(idx_ref, col_ref, dc_ref, b_ref, dv_ref, acc_ref,
                           *, n_tiles: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # [h, bn] x [w, bn]^T -> [h, w]
    acc_ref[...] += jax.lax.dot_general(
        dc_ref[...], b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _flush():
        dv_ref[0] = acc_ref[...].astype(dv_ref.dtype)


def bcsr_sddmm_row_loop(dc: jnp.ndarray, b: jnp.ndarray,
                        flat_idx: jnp.ndarray, flat_col: jnp.ndarray,
                        n_block_rows: int, nnzb: int, h: int, w: int, *,
                        bn: int = 512, out_dtype=None,
                        interpret: bool = False):
    """Static-schedule SDDMM: the 2D (block-row x slot) grid of
    ``bcsr_spmm_row_loop``, sampling ``dC @ B^T`` at the stored blocks.

    flat_idx [nbr*max_bpr]  OUTPUT entry per (row, slot); padding slots
                            point at the sentinel entry ``nnzb`` (their
                            product is computed and discarded — faithful
                            static waste on short rows).
    flat_col [nbr*max_bpr]  block-col per (row, slot) (padding -> 0)

    Returns ``[nnzb, h, w]`` (the sentinel row is sliced off).
    """
    M, N = dc.shape
    K, _ = b.shape
    assert M % h == 0 and K % w == 0
    bn = min(bn, N)
    assert N % bn == 0
    out_dtype = out_dtype or dc.dtype
    max_bpr = flat_idx.shape[0] // n_block_rows
    n_tiles = N // bn
    grid = (n_block_rows, max_bpr, n_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((h, bn),
                         lambda i, t, j, idx_ref, col_ref: (i, j)),
            pl.BlockSpec((w, bn),
                         lambda i, t, j, idx_ref, col_ref:
                         (col_ref[i * max_bpr + t], j)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, w), lambda i, t, j, idx_ref, col_ref:
            (idx_ref[i * max_bpr + t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, w), jnp.float32)],
    )
    kernel = functools.partial(_sddmm_row_loop_kernel, n_tiles=n_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nnzb + 1, h, w), out_dtype),
        interpret=interpret,
        name="smat_sddmm_row_loop",
    )(flat_idx, flat_col, dc, b)
    return out[:nnzb]
