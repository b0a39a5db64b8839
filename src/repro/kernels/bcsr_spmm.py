"""Pallas TPU kernels for BCSR SpMM — the paper's contribution, MXU-native.

Four kernels:

  * ``bcsr_spmm_nnz_stream``  — production forward.  One grid step owns a
    *row panel*: ``R`` consecutive block-rows (``R * h`` = 128 rows of C)
    for one N tile, with an f32 accumulator of that size in VMEM.  The
    nonzero-block list is scalar-prefetched into SMEM; ``vals`` and B stay
    in HBM, and the step streams its panel's blocks through a ring of VMEM
    slots with DMAs it issues itself, keeping up to 15 blocks' copies in
    flight, across panel and N-tile edges.  Rows of any length cost only their own blocks
    (beyond-paper: this removes SMaT's ``dc2`` worst case), and the fixed
    cost of a grid step is paid once per panel, not once per block.

  * ``bcsr_spmm_row_loop``    — the paper-faithful *static schedule*: one
    output tile per (block-row x N-tile) grid cell, looping to
    ``max_blocks_per_row`` with masking, exactly like SMaT's warp-per-C-tile
    2D schedule (wasted iterations on short rows; used as the faithful
    baseline in benchmarks).

  * ``bcsr_sddmm``            — block-sampled dense-dense product
    (``X @ Y^T`` evaluated only at the stored blocks) on the same row
    panels: one grid step holds its panel's rows of X over the whole
    contraction in VMEM and streams its blocks' Y slabs through a ring of
    VMEM slots, each block one f32 contraction written out by DMA; a
    contraction too wide for VMEM is cut into chunks, one launch each.
    It is both the backward pass of SpMM (dW of a sparse weight) and the
    forward of the public ``ops.sddmm`` — the score kernel of block-sparse
    attention and the dropless MoE's gate and up products.

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
_PANEL_ROWS = 128            # rows of C one grid step owns: R = 128 // h
_RING_BYTES = 4 * 2 ** 20    # VMEM for the block copies in flight
# 8 to 64 slots time alike on a TPU v5e at HPCG 64^3 (N = 8 and 512); 4 is
# slower, so 16 leaves room.
_RING_MAX = 16


def panel_block_rows(h: int, n_block_rows: int) -> int:
    """R, the block-rows of one panel: ``R * h`` is 128 rows of C, or one
    block-row where blocks are taller, and never more than the matrix has."""
    return max(1, min(_PANEL_ROWS // h, n_block_rows))


def ring_depth(h: int, w: int, bn: int, nnzb: int, a_bytes: int = 2,
               b_bytes: int = 2) -> int:
    """Slots of the DMA ring: the largest power of two (at most 16 and at
    most ``nnzb``) whose A blocks and ``(w, bn)`` B tiles fit 4 MiB."""
    per_block = h * w * a_bytes + w * bn * b_bytes
    fit = max(1, min(_RING_MAX, nnzb, _RING_BYTES // per_block))
    return 1 << (fit.bit_length() - 1)


def nnz_stream_vmem_bytes(h: int, w: int, bn: int, n_block_rows: int,
                          nnzb: int) -> int:
    """VMEM working set of ``bcsr_spmm_nnz_stream`` on bf16 operands: the
    f32 accumulator, the double-buffered output tile and the DMA ring."""
    rows = panel_block_rows(h, n_block_rows) * h
    depth = ring_depth(h, w, bn, nnzb)
    return rows * bn * (4 + 2 * 2) + depth * (h * w + w * bn) * 2


def _nnz_stream_kernel(row_ref, col_ref, vals_hbm, b_hbm, o_ref, acc_ref,
                       a_buf, b_buf, sem, cursor, *, nnzb: int, h: int,
                       w: int, bn: int, panel_rows: int, depth: int):
    """One grid step = one (N tile j, panel p).  Blocks form one stream
    over the whole grid, item ``g = j * nnzb + s`` in ring slot
    ``g % depth``; each step waits for its panel's blocks in stored order
    and starts the copies ``depth - 1`` items ahead, into the next panel
    and the next N tile, so the ring never drains at a step's edge.  The
    steps run in grid order, so a panel's blocks start where the previous
    panel's ended (``cursor``) and run while their block-row lies in it."""
    j, p = pl.program_id(0), pl.program_id(1)
    n_tiles = pl.num_programs(0)
    mask = depth - 1

    def copies(s, jj, slot, col):
        return (pltpu.make_async_copy(vals_hbm.at[s], a_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(
                    b_hbm.at[pl.ds(col * w, w), pl.ds(jj * bn, bn)],
                    b_buf.at[slot], sem.at[1, slot]))

    def start(s, jj, slot):
        for c in copies(s, jj, slot, col_ref[s]):
            c.start()

    @pl.when(jnp.logical_and(j == 0, p == 0))
    def _prime():
        def body(g, carry):
            start(g, 0, g)
            return carry
        jax.lax.fori_loop(0, depth - 1, body, 0)

    @pl.when(p == 0)
    def _rewind():
        cursor[0] = 0

    acc_ref[...] = jnp.zeros_like(acc_ref)
    base = p * panel_rows

    def in_panel(s):
        row = row_ref[jnp.minimum(s, nnzb - 1)]
        return jnp.logical_and(s < nnzb, row < base + panel_rows)

    def body(s):
        g = j * nnzb + s
        ahead = s + (depth - 1)
        wrap = ahead >= nnzb
        s2 = jnp.where(wrap, ahead - nnzb, ahead)
        j2 = jnp.where(wrap, j + 1, j)

        @pl.when(j2 < n_tiles)
        def _prefetch():
            start(s2, j2, (g + depth - 1) & mask)

        slot = g & mask
        for c in copies(0, 0, slot, 0):
            c.wait()
        off = pl.multiple_of((row_ref[s] - base) * h, h)
        acc_ref[pl.ds(off, h), :] += jax.lax.dot(
            a_buf[slot], b_buf[slot], preferred_element_type=jnp.float32)
        return s + 1

    cursor[0] = jax.lax.while_loop(in_panel, body, cursor[0])
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bcsr_spmm_nnz_stream(vals: jnp.ndarray, row_ids: jnp.ndarray,
                         col_ids: jnp.ndarray, b: jnp.ndarray,
                         n_block_rows: int, *, bn: int = 512,
                         out_dtype=None, interpret: bool = False):
    """C[nbr*h, N] = A_bcsr @ B.  Entries must be sorted row-major and every
    block-row must contain >= 1 entry (``BCSR.ensure_nonempty_rows``).
    Each row of C sums its blocks' products in stored order, in f32."""
    nnzb, h, w = vals.shape
    K, N = b.shape
    assert K % w == 0, (K, w)
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    out_dtype = out_dtype or b.dtype
    r = panel_block_rows(h, n_block_rows)
    n_panels = -(-n_block_rows // r)
    depth = ring_depth(h, w, bn, nnzb, vals.dtype.itemsize,
                       b.dtype.itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // bn, n_panels),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),    # vals stay in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],   # B stays in HBM
        out_specs=pl.BlockSpec((r * h, bn), lambda j, p, *_: (p, j)),
        scratch_shapes=[pltpu.VMEM((r * h, bn), jnp.float32),
                        pltpu.VMEM((depth, h, w), vals.dtype),
                        pltpu.VMEM((depth, w, bn), b.dtype),
                        pltpu.SemaphoreType.DMA((2, depth)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_nnz_stream_kernel, nnzb=nnzb, h=h, w=w,
                               bn=bn, panel_rows=r, depth=depth)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_panels * r * h, N), out_dtype),
        # the ring and the cursor carry from one step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="smat_spmm_nnz_stream",
    )(row_ids, col_ids, vals, b)
    return out[: n_block_rows * h]


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
def sddmm_chunks(h: int, w: int, n: int, n_block_rows: int,
                 x_bytes: int = 2, y_bytes: int = 2) -> tuple[int, int]:
    """``(chunk, n_chunks)``: the contraction columns one grid step of
    ``bcsr_sddmm`` holds.  All of N while the panel's X, double-buffered,
    fits half the ring's 4 MiB and a Y slab a quarter (so the ring keeps
    four slots or more); else the fewest 128-column chunks that do, the
    last padded with zeros."""
    rows = panel_block_rows(h, n_block_rows) * h
    fit = _RING_BYTES // (4 * max(rows * x_bytes, w * y_bytes))
    if n <= fit:
        return n, 1
    fit = max(128, fit // 128 * 128)
    n_chunks = -(-n // fit)
    return -(-n // (128 * n_chunks)) * 128, n_chunks


def sddmm_stream_vmem_bytes(h: int, w: int, n: int, n_block_rows: int,
                            nnzb: int) -> int:
    """VMEM working set of ``bcsr_sddmm`` on bf16 operands: the panel's X
    (double-buffered), the ring of Y slabs and the ring of output blocks
    (f32 where the contraction is chunked)."""
    rows = panel_block_rows(h, n_block_rows) * h
    chunk, n_chunks = sddmm_chunks(h, w, n, n_block_rows)
    depth = ring_depth(h, w, chunk, nnzb, a_bytes=0)
    out_bytes = 2 if n_chunks == 1 else 4
    return 2 * rows * chunk * 2 + depth * (w * chunk * 2 + h * w * out_bytes)


def _sddmm_stream_kernel(row_ref, col_ref, x_ref, y_hbm, o_hbm, y_buf,
                         o_buf, sem, cursor, *, nnzb: int, h: int, w: int,
                         col0: int, chunk: int, panel_rows: int, depth: int):
    """One grid step = one panel p, whose X rows (``[R * h, chunk]``) the
    BlockSpec pipeline holds in VMEM.  Its blocks, in stored order, wait
    for their ``[w, chunk]`` Y slab in ring slot ``s % depth``, contract
    it with their ``h`` rows of the panel in f32, and copy the ``[h, w]``
    product out of the same slot of an output ring; the Y copies run
    ``depth - 1`` blocks ahead, across panel edges.  The steps run in
    grid order, so a panel's blocks start where the previous panel's
    ended (``cursor``)."""
    p = pl.program_id(0)
    mask = depth - 1

    def y_copy(col, slot):
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(col * w, w), pl.ds(col0, chunk)], y_buf.at[slot],
            sem.at[0, slot])

    def o_copy(s, slot):
        return pltpu.make_async_copy(o_buf.at[slot], o_hbm.at[s],
                                     sem.at[1, slot])

    @pl.when(p == 0)
    def _prime():
        cursor[0] = 0

        def body(s, carry):
            y_copy(col_ref[s], s).start()
            return carry
        jax.lax.fori_loop(0, depth - 1, body, 0)

    base = p * panel_rows

    def in_panel(s):
        row = row_ref[jnp.minimum(s, nnzb - 1)]
        return jnp.logical_and(s < nnzb, row < base + panel_rows)

    def body(s):
        ahead = s + (depth - 1)

        @pl.when(ahead < nnzb)
        def _prefetch():
            y_copy(col_ref[ahead], ahead & mask).start()

        slot = s & mask
        y_copy(0, slot).wait()
        off = pl.multiple_of((row_ref[s] - base) * h, h)
        # [h, chunk] x [w, chunk]^T -> [h, w]
        prod = jax.lax.dot_general(
            x_ref[pl.ds(off, h), :], y_buf[slot],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(s >= depth)
        def _slot_free():
            o_copy(0, slot).wait()     # block s - depth has left the slot

        o_buf[slot] = prod.astype(o_buf.dtype)
        o_copy(s, slot).start()
        return s + 1

    cursor[0] = jax.lax.while_loop(in_panel, body, cursor[0])

    @pl.when(p == pl.num_programs(0) - 1)
    def _drain():            # depth <= nnzb: one copy in flight per slot
        for slot in range(depth):
            o_copy(0, slot).wait()


def bcsr_sddmm(dc: jnp.ndarray, b: jnp.ndarray, row_ids: jnp.ndarray,
               col_ids: jnp.ndarray, h: int, w: int, *, out_dtype=None,
               interpret: bool = False):
    """dVals[s] = dC[block row_ids[s]] @ B[block col_ids[s]]^T — the sparse
    weight gradient, computed only at the stored blocks.  Entries must be
    sorted row-major; empty block-rows are allowed.  Each block is one f32
    contraction over all of N, or a sum of f32 chunk products in chunk
    order where the panel's rows of dC do not fit VMEM whole
    (``sddmm_chunks``)."""
    M, N = dc.shape
    K, _ = b.shape
    assert M % h == 0 and K % w == 0, (M, K, h, w)
    nnzb = row_ids.shape[0]
    out_dtype = out_dtype or dc.dtype
    if nnzb == 0:
        return jnp.zeros((0, h, w), out_dtype)
    nbr = M // h
    r = panel_block_rows(h, nbr)
    n_panels = -(-nbr // r)
    chunk, n_chunks = sddmm_chunks(h, w, N, nbr, dc.dtype.itemsize,
                                   b.dtype.itemsize)
    if chunk * n_chunks > N:
        pad = ((0, 0), (0, chunk * n_chunks - N))
        dc, b = jnp.pad(dc, pad), jnp.pad(b, pad)
    depth = ring_depth(h, w, chunk, nnzb, a_bytes=0,
                       b_bytes=b.dtype.itemsize)
    part_dtype = out_dtype if n_chunks == 1 else jnp.float32

    def call(k):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_panels,),
            in_specs=[pl.BlockSpec((r * h, chunk), lambda p, *_: (p, k)),
                      pl.BlockSpec(memory_space=pl.ANY)],   # B stays in HBM
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((depth, w, chunk), b.dtype),
                            pltpu.VMEM((depth, h, w), part_dtype),
                            pltpu.SemaphoreType.DMA((2, depth)),
                            pltpu.SMEM((1,), jnp.int32)],
        )
        kernel = functools.partial(
            _sddmm_stream_kernel, nnzb=nnzb, h=h, w=w, col0=k * chunk,
            chunk=chunk, panel_rows=r, depth=depth)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nnzb, h, w), part_dtype),
            # the rings and the cursor carry from one step into the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="smat_sddmm_nnz_stream",
        )(row_ids, col_ids, dc, b)

    out = call(0)
    for k in range(1, n_chunks):
        out = out + call(k)
    return out.astype(out_dtype)


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
