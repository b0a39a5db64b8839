"""Fused one-kernel block-sparse attention (flash-style) — SDDMM, block
softmax and the context SpMM in a SINGLE Pallas launch.

The composed path (PR 5) is three dispatches per head:

    scores = ops.sddmm(mask, Q, K)   # materializes [nnzb, h, w]
    probs  = block_softmax(scores)   # materializes [nnzb, h, w] again
    ctx    = ops.spmm(probs, V)

``bcsr_attn_fused`` walks a static (block-row x slot) schedule
(``static_schedule``: padding slots point at a sentinel entry) and never
writes a score or prob block to HBM: each grid cell recomputes its Q K^T
block on the fly and folds it into per-query-block running state held in
VMEM scratch — O(L * d) memory and one kernel launch instead of three.

**Bit-for-bit contract.**  The fused forward is pinned bitwise-equal (f32)
to the composed SDDMM -> ``block_softmax`` -> SpMM path.  A classic
flash-attention *rescaling* online softmax cannot satisfy that pin (its
running renormalisation reassociates the sums), so the kernel runs THREE
passes over the block-row's slots inside one launch — grid
``(G, n_block_rows, 3, max_bpr)`` with the slot axis innermost:

    phase 0   running row max     m  <- max(m, max(logits))
    phase 1   denominator         l  <- l + sum(exp(logits - m))
    phase 2   context             acc <- acc + (exp(logits - m) / l) @ V

Every elementary op replays the composed path exactly: the score block is
tiled over the contraction axis in the same order as ``ops._sddmm_impl``,
masked elements go to the same ``NEG_INF`` sentinel, the max is
order-insensitive, and phases 1/2 accumulate left-to-right in entry order
— which is bitwise what ``jax.ops.segment_sum`` computes for row-major
sorted segment ids.  Sentinel slots contribute exact ``+0.0`` terms, so
the static waste never perturbs the numbers.

One carve-out: the optional ``cap`` tanh soft-clip.  XLA's ``tanh``
lowering is not bitwise-stable across fusion contexts (even ``jit(f)``
vs eager ``f`` of the SAME composed graph differ in the last ulp), so
capped attention is pinned at float tolerance instead — the bit-for-bit
contract covers the standard ``cap=None`` path.

Backward is NOT fused: ``models.attention`` pairs this forward with the
composed dual-VJP path (SpMM and SDDMM are mutual duals), which the
bit-for-bit forward pin makes gradient-consistent.  A recompute-based
fused backward is an explicit non-goal (ROADMAP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.attention_mask import NEG_INF
from repro.kernels.ops import _clamp_bn


def static_schedule(row_ids, col_ids, n_block_rows: int, max_bpr: int):
    """``(flat_idx, flat_col)``, the kernel's static schedule, from the
    row-major sorted entry list (host numpy): per (block-row, slot) the
    entry index and block-col, ``n_block_rows * max_bpr`` slots in all.
    Padding slots hold the sentinel entry ``nnzb`` and block-col 0.

    >>> static_schedule([0, 1, 1], [0, 0, 1], 2, 2)
    (array([0, 3, 1, 2], dtype=int32), array([0, 0, 0, 1], dtype=int32))
    """
    row_ids = np.asarray(row_ids, np.int64)
    nnzb = row_ids.shape[0]
    counts = np.bincount(row_ids, minlength=n_block_rows)
    rowptr = np.concatenate([[0], np.cumsum(counts)])
    pos = row_ids * max_bpr + np.arange(nnzb) - rowptr[row_ids]
    flat_idx = np.full(n_block_rows * max_bpr, nnzb, np.int32)
    flat_col = np.zeros(n_block_rows * max_bpr, np.int32)
    flat_idx[pos] = np.arange(nnzb, dtype=np.int32)
    flat_col[pos] = col_ids
    return flat_idx, flat_col


def _attn_fused_kernel(idx_ref, col_ref, q_ref, k_ref, v_ref, em_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, max_bpr: int,
                       n_d_tiles: int, bn_d: int, n_v_tiles: int, bn_v: int,
                       scale: float, cap):
    p = pl.program_id(2)          # phase: 0 max | 1 denom | 2 accumulate
    t = pl.program_id(3)          # slot within the block-row's schedule
    first = t == 0
    last = t == max_bpr - 1

    q = q_ref[0]                  # [h, dpad]
    kb = k_ref[0]                 # [w, dpad]
    em = em_ref[0] != 0.0         # [h, w]; sentinel block -> all False

    # score block, tiled over the contraction axis exactly like the
    # composed SDDMM (same per-tile dots, same accumulation order)
    s = jnp.zeros(em.shape, jnp.float32)
    for j in range(n_d_tiles):
        sl = slice(j * bn_d, (j + 1) * bn_d)
        s += jax.lax.dot_general(
            q[:, sl], kb[:, sl],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    s = s * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    logits = jnp.where(em, s, NEG_INF)

    @pl.when(jnp.logical_and(p == 0, first))
    def _init_m():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, m_scr.dtype)

    @pl.when(p == 0)
    def _max():
        m_scr[...] = jnp.maximum(m_scr[...], jnp.max(logits, axis=1)[:, None])

    @pl.when(jnp.logical_and(p == 0, last))
    def _clamp_m():   # rows with no valid element (block_softmax clamp)
        m_scr[...] = jnp.maximum(m_scr[...], -1e30)

    @pl.when(jnp.logical_and(p == 1, first))
    def _init_l():
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(p == 1)
    def _denom():
        z = jnp.exp(logits - m_scr[:, :1])
        z = jnp.where(em, z, 0.0)
        l_scr[...] += z.sum(axis=1)[:, None]

    @pl.when(jnp.logical_and(p == 1, last))
    def _clamp_l():
        l_scr[...] = jnp.maximum(l_scr[...], 1e-30)

    @pl.when(jnp.logical_and(p == 2, first))
    def _init_acc():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(p == 2)
    def _ctx():
        z = jnp.exp(logits - m_scr[:, :1])
        z = jnp.where(em, z, 0.0)
        pb = z / l_scr[:, :1]
        for j in range(n_v_tiles):
            sl = slice(j * bn_v, (j + 1) * bn_v)
            acc_scr[:, sl] += jax.lax.dot(
                pb, v_ref[0][:, sl], preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(p == 2, last))
    def _flush():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def bcsr_attn_fused(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    emask: jnp.ndarray, flat_idx: jnp.ndarray,
                    flat_col: jnp.ndarray, *, n_block_rows: int,
                    n_block_cols: int, block, scale: float,
                    cap=None, bn: int = 512, out_dtype=None,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused block-sparse attention over a static BCSR mask schedule.

    q, k, v   ``[G, Lq, d]`` / ``[G, Lk, d]`` / ``[G, Lk, dv]`` — G folded
              (batch * heads) instances sharing one mask structure.
    emask     ``[nnzb, h, w]`` float 0/1 — valid (stored AND allowed AND
              non-padding) elements of each stored block, entries sorted
              row-major.  A zero sentinel block is appended internally.
    flat_idx  ``[nbr * max_bpr]`` entry index per (block-row, slot);
              padding slots hold the sentinel index ``nnzb``
              (the ``static_schedule`` layout).
    flat_col  ``[nbr * max_bpr]`` block-col per (block-row, slot).
    scale     applied to the scores before the optional ``cap`` tanh
              soft-clip, exactly like ``models.attention.block_softmax``.

    Returns ``[G, Lq, dv]``; masked query rows get all-zero context.

    >>> import numpy as np, jax, jax.numpy as jnp
    >>> from repro.kernels import bcsr_attn
    >>> L, d = 8, 4
    >>> rng = np.random.default_rng(0)
    >>> q, k, v = (jnp.asarray(rng.standard_normal((1, L, d)), jnp.float32)
    ...            for _ in range(3))
    >>> # causal mask on a 2x2 block grid: stored blocks (0,0) (1,0) (1,1)
    >>> qpos = np.arange(L)[:, None]; kpos = np.arange(L)[None, :]
    >>> elem = (kpos <= qpos).reshape(2, 4, 2, 4).transpose(0, 2, 1, 3)
    >>> emask = elem[[0, 1, 1], [0, 0, 1]].astype(np.float32)
    >>> flat_idx = np.array([0, 3, 1, 2], np.int32)   # sentinel = nnzb = 3
    >>> flat_col = np.array([0, 0, 0, 1], np.int32)
    >>> out = bcsr_attn.bcsr_attn_fused(
    ...     q, k, v, emask, flat_idx, flat_col, n_block_rows=2,
    ...     n_block_cols=2, block=(4, 4), scale=0.5, interpret=True)
    >>> out.shape
    (1, 8, 4)
    >>> s = (q[0] @ k[0].T) * 0.5
    >>> p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -2.0e38), axis=-1)
    >>> bool(jnp.allclose(out[0], p @ v[0], atol=1e-5))
    True
    """
    G, Lq, dq = q.shape
    _, Lk, dk = k.shape
    dv = v.shape[2]
    h, w = block
    nnzb = emask.shape[0]
    max_bpr = flat_idx.shape[0] // n_block_rows
    assert flat_idx.shape[0] == n_block_rows * max_bpr and max_bpr > 0
    assert n_block_rows * h >= Lq and n_block_cols * w >= Lk
    out_dtype = out_dtype or q.dtype

    # pad the contraction axis exactly like the composed ops._sddmm_impl:
    # common width for q and k, tiled at the clamped bn
    bn_d = _clamp_bn(bn, max(dq, dk))
    dpad = max(dq + ((-dq) % bn_d), dk + ((-dk) % bn_d))
    bn_d = min(bn_d, dpad)
    # ...and the V panel like the composed context SpMM (ops._fwd_impl)
    bn_v = _clamp_bn(bn, dv)
    vpad = dv + ((-dv) % bn_v)
    bn_v = min(bn_v, vpad)

    qp = jnp.pad(q, ((0, 0), (0, n_block_rows * h - Lq), (0, dpad - dq)))
    kp = jnp.pad(k, ((0, 0), (0, n_block_cols * w - Lk), (0, dpad - dk)))
    vp = jnp.pad(v, ((0, 0), (0, n_block_cols * w - Lk), (0, vpad - dv)))
    em_ext = jnp.concatenate(
        [jnp.asarray(emask, jnp.float32),
         jnp.zeros((1, h, w), jnp.float32)], axis=0)

    grid = (G, n_block_rows, 3, max_bpr)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # Q block-row i of instance g (constant across phases/slots —
            # one DMA per block-row)
            pl.BlockSpec((1, h, dpad),
                         lambda g, i, p, t, idx_ref, col_ref: (g, i, 0)),
            # K / V panels: data-dependent DMA via the prefetched schedule
            pl.BlockSpec((1, w, dpad),
                         lambda g, i, p, t, idx_ref, col_ref:
                         (g, col_ref[i * max_bpr + t], 0)),
            pl.BlockSpec((1, w, vpad),
                         lambda g, i, p, t, idx_ref, col_ref:
                         (g, col_ref[i * max_bpr + t], 0)),
            # element mask of the scheduled entry (sentinel -> zero block)
            pl.BlockSpec((1, h, w),
                         lambda g, i, p, t, idx_ref, col_ref:
                         (idx_ref[i * max_bpr + t], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, vpad), lambda g, i, p, t, idx_ref, col_ref: (g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),   # running row max
            pltpu.VMEM((h, 128), jnp.float32),   # running denominator
            pltpu.VMEM((h, vpad), jnp.float32),  # context accumulator
        ],
    )
    kernel = functools.partial(
        _attn_fused_kernel, max_bpr=max_bpr, n_d_tiles=dpad // bn_d,
        bn_d=bn_d, n_v_tiles=vpad // bn_v, bn_v=bn_v, scale=scale, cap=cap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, n_block_rows * h, vpad),
                                       out_dtype),
        interpret=interpret,
        name="smat_attn_fused",
    )(flat_idx, flat_col, qp, kp, vp, em_ext)
    return out[:, :Lq, :dv]
