"""Public jit-ready SpMM ops: backend dispatch + custom VJP.

``SparseMatrix`` is the device-side, kernel-ready form of a host ``BCSR``:
entries padded so every block-row is nonempty (nnz-stream kernel invariant),
plus the precomputed transpose structure used by the backward pass
(dX = A^T dY).  It is a registered pytree whose integer index arrays ride
along as leaves (sharded/replicated like any other param) while the shape
metadata is static.

Backends:
  * ``pallas``   — the nnz-streamed TPU kernel (``interpret=True`` on CPU).
                   ``nnz_stream`` is accepted as an alias.
  * ``xla``      — pure-jnp reference path (shardable; used by the
                   512-device dry-run and as the CI oracle).
  * ``dense``    — materialize the padded dense matrix and ``jnp.dot`` (the
                   cuBLAS comparison arm of the paper).
  * ``auto``     — dispatch through ``repro.kernels.autotune``: the variant
                   registry picks (backend, bn) from the matrix's stats
                   fingerprint (cached analytic pick, or a previously
                   measured micro-sweep result).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcsr as bcsr_lib
from repro.kernels import bcsr_spmm as pk
from repro.kernels import ref


# ---------------------------------------------------------------------- types
class SparseArrays(NamedTuple):
    """Device arrays of a BCSR operand (pytree leaves).

    ``row_perm`` / ``inv_perm`` carry the block-densifying row permutation
    (paper IV-C) applied by ``prepare_sparse(reorder=...)``: the stored
    blocks are those of A' = P A, and ``spmm`` returns C = P^T (A' B) so
    callers always see ORIGINAL row order.  They default to None for
    hand-built operands (identity semantics)."""
    vals: jnp.ndarray        # [nnzb, h, w] — the only trainable leaf
    row_ids: jnp.ndarray     # [nnzb] int32, sorted row-major
    col_ids: jnp.ndarray     # [nnzb] int32
    real_mask: jnp.ndarray   # [nnzb] bool — False for padding entries
    t_perm: jnp.ndarray      # [nnzb_t] int32 into vals (nnzb == sentinel zero)
    t_row_ids: jnp.ndarray   # [nnzb_t] int32 (block-rows of A^T)
    t_col_ids: jnp.ndarray   # [nnzb_t] int32
    row_perm: Optional[jnp.ndarray] = None   # [M] int32: A'[i] = A[row_perm[i]]
    inv_perm: Optional[jnp.ndarray] = None   # [M] int32: argsort(row_perm)


@dataclasses.dataclass(frozen=True)
class SparseMeta:
    """Static (hashable) metadata of a sparse operand.

    The trailing stats fields feed the autotuner's fingerprint (and the
    fused attention kernel, which sizes its static (block-row x slot) grid
    by ``max_bpr``).  They default to "unknown" so hand-built metas (e.g.
    the dry-run's dims-only ``sparse_linear_specs``) keep working — the
    autotuner simply won't propose the fused attention for those.
    Because the whole dataclass is hashable, a meta is safe to close over
    inside jit traces and to ride through scan-stacked model layers as
    STATIC aux data (never as a pytree leaf) — the contract
    ``docs/ARCHITECTURE.md`` spells out.
    """
    shape: Tuple[int, int]          # logical (M, K)
    block: Tuple[int, int]          # (h, w)
    n_block_rows: int
    n_block_cols: int
    nnzb: int
    nnzb_t: int
    max_bpr: int = 0                # max blocks per block-row (0 = unknown)
    padding_ratio_pct: int = 0      # % of stored values that are zeros
    bpr_cv_pct: int = 0             # blocks-per-row std/mean, in %
    reorder: str = "identity"       # row-permutation scheme baked into vals
                                    # (autotune fingerprints on it: permuted
                                    # matrices have different bpr skew)
    n_shards: int = 1               # 1 = whole matrix; >1 = this meta is one
                                    # shard of a row-partitioned operand
                                    # (launch.dist_spmm) — fingerprinted so
                                    # per-shard picks never alias the
                                    # unsharded twin's cache entries
    built: str = "host"             # host (prepare_sparse) | device
                                    # (device_sparse: rebuilt every step,
                                    # static capacity, ``auto`` is static)


# accepted aliases -> canonical SpmmConfig.backend strings
_BACKEND_ALIASES = {"nnz_stream": "pallas"}
BACKENDS = ("pallas", "xla", "dense")


@dataclasses.dataclass(frozen=True)
class SpmmConfig:
    backend: str = "pallas"         # pallas | xla | dense
    bn: int = 512                   # N-tile width for the Pallas grid
    interpret: bool = False
    out_dtype: Optional[str] = None


# ------------------------------------------------------------------- prepare
def _prepare_sparse_host(a: bcsr_lib.BCSR, *, reorder: str,
                         reorder_granularity: str, tau: float,
                         max_candidates: Optional[int], n_shards: int):
    """Host-side (numpy) portion of ``prepare_sparse``: permute, pad,
    build the transpose structure, and compute the static meta.  Returns
    ``(host_arrays_dict, meta)``; ``prepare_sparse`` converts the arrays
    to device, ``prepare_sparse_meta`` keeps only the meta (the static
    structure-metadata pipeline the model layers dispatch on)."""
    from repro.core import permute as permute_lib  # local: import cycle
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    with obs_trace.span("prepare.reorder", scheme=reorder,
                        granularity=reorder_granularity), \
            obs_metrics.timer("prepare.seconds", stage="reorder"):
        a, row_perm_np = permute_lib.permute_bcsr(
            a, reorder, tau=tau, max_candidates=max_candidates,
            n_shards=n_shards, granularity=reorder_granularity)
    # padding entries are tagged explicitly by ensure_nonempty_rows (before
    # its lexsort), so genuinely-zero original blocks — e.g. from
    # random_bcsr(fill_density<1) — keep real_mask=True and stay trainable.
    with obs_trace.span("prepare.meta"), \
            obs_metrics.timer("prepare.seconds", stage="meta"):
        a_p, real_mask = a.ensure_nonempty_rows(return_mask=True)

        # ---- transpose structure (entries of A^T in A^T row-major order) --
        order = np.lexsort((a_p.row_ids, a_p.col_ids))
        t_perm = order.astype(np.int32)
        t_row_ids = a_p.col_ids[order].astype(np.int32)
        t_col_ids = a_p.row_ids[order].astype(np.int32)
        # pad A^T's empty block-rows with the sentinel zero block (index
        # nnzb)
        n_brows_t = a_p.n_block_cols
        present = np.zeros(n_brows_t, dtype=bool)
        present[t_row_ids] = True
        empty = np.flatnonzero(~present).astype(np.int32)
        if empty.size:
            t_perm = np.concatenate(
                [t_perm, np.full(empty.size, a_p.nnzb, np.int32)])
            t_row_ids = np.concatenate([t_row_ids, empty])
            t_col_ids = np.concatenate([t_col_ids,
                                        np.zeros(empty.size, np.int32)])
            order_t = np.lexsort((t_col_ids, t_row_ids))
            t_perm, t_row_ids, t_col_ids = (
                t_perm[order_t], t_row_ids[order_t], t_col_ids[order_t])

        inv_perm_np = permute_lib.invert_perm(row_perm_np)
        max_bpr, pad_pct, cv_pct = a_p.dispatch_stats()
        meta = SparseMeta(shape=a_p.shape, block=a_p.block,
                          n_block_rows=a_p.n_block_rows,
                          n_block_cols=a_p.n_block_cols,
                          nnzb=a_p.nnzb, nnzb_t=int(t_row_ids.shape[0]),
                          max_bpr=max_bpr, padding_ratio_pct=pad_pct,
                          bpr_cv_pct=cv_pct, reorder=reorder)
    host = {
        "vals": a_p.vals,
        "row_ids": a_p.row_ids,
        "col_ids": a_p.col_ids,
        "real_mask": real_mask,
        "t_perm": t_perm,
        "t_row_ids": t_row_ids,
        "t_col_ids": t_col_ids,
        "row_perm": row_perm_np,
        "inv_perm": inv_perm_np,
    }
    obs_trace.event("prepare.done", shape=meta.shape, block=meta.block,
                    nnzb=meta.nnzb, nnzb_t=meta.nnzb_t,
                    max_bpr=meta.max_bpr, reorder=reorder)
    return host, meta


def prepare_sparse(a: bcsr_lib.BCSR, dtype=jnp.bfloat16, *,
                   reorder: str = "identity",
                   reorder_granularity: str = "element",
                   tau: float = 0.7, max_candidates: Optional[int] = None,
                   n_shards: int = 8
                   ) -> Tuple[SparseArrays, SparseMeta]:
    """Host BCSR -> kernel-ready device arrays + static meta.  Returns
    once the arrays are on the device; each stage sets its gauge
    ``prepare.seconds{stage=reorder|meta|to_device}``.

    ``reorder`` applies a block-densifying row permutation first (any
    scheme in ``core.permute.SCHEMES`` that yields a pure row permutation:
    ``jaccard`` | ``rcm`` | ``shard_balance`` | ``identity``).  The
    permutation is transparent downstream: ``spmm`` un-permutes its output
    (C = P^T (A' B)) and the custom VJP carries P through dB and dvals, so
    results match ``reorder="identity"`` while the kernel streams the
    denser A'.  ``reorder_granularity="element"`` (default) re-blocks the
    permuted NONZERO structure — explicitly-stored zero blocks do not
    survive it; ``"block_row"`` permutes whole block-rows instead (nnzb
    and all stored entries preserved — the model-weight path, where
    stacked leaf shapes must be static and zero blocks stay trainable).

    The returned ``meta`` carries the POST-reorder structure stats
    (``max_bpr``, padding, skew) — the autotune fingerprint is derived
    from the permuted structure, so clustering that densifies block-rows
    shrinks ``max_bpr``.

    Example (a block-diagonal 32x32 with 8x8 blocks):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import ops
    >>> dense = np.kron(np.eye(4, dtype=np.float32), np.ones((8, 8)))
    >>> a = bcsr_lib.from_dense(dense.astype(np.float32), (8, 8))
    >>> arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    >>> (meta.nnzb, meta.max_bpr)
    (4, 1)
    """
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    host, meta = _prepare_sparse_host(
        a, reorder=reorder, reorder_granularity=reorder_granularity,
        tau=tau, max_candidates=max_candidates, n_shards=n_shards)
    with obs_trace.span("prepare.to_device"), \
            obs_metrics.timer("prepare.seconds", stage="to_device"):
        arrays = SparseArrays(
            vals=jnp.asarray(host["vals"], dtype=dtype),
            row_ids=jnp.asarray(host["row_ids"], dtype=jnp.int32),
            col_ids=jnp.asarray(host["col_ids"], dtype=jnp.int32),
            real_mask=jnp.asarray(host["real_mask"]),
            t_perm=jnp.asarray(host["t_perm"], dtype=jnp.int32),
            t_row_ids=jnp.asarray(host["t_row_ids"], dtype=jnp.int32),
            t_col_ids=jnp.asarray(host["t_col_ids"], dtype=jnp.int32),
            row_perm=jnp.asarray(host["row_perm"], dtype=jnp.int32),
            inv_perm=jnp.asarray(host["inv_perm"], dtype=jnp.int32),
        )
        # the stage covers the transfer itself; the caller waits for the
        # arrays anyway
        jax.block_until_ready(arrays)
    return arrays, meta


def prepare_sparse_meta(a: bcsr_lib.BCSR, *, reorder: str = "identity",
                        reorder_granularity: str = "element",
                        tau: float = 0.7,
                        max_candidates: Optional[int] = None,
                        n_shards: int = 8) -> SparseMeta:
    """The static meta ``prepare_sparse`` would return, WITHOUT building
    device arrays — bit-identical by construction (same host pipeline).

    This is the backbone of the static structure-metadata pipeline: model
    layers re-derive the true post-reorder stats of a deterministic weight
    pattern at trace time (``core.sparse_linear.sparse_linear_meta``
    memoizes it), so ``backend="auto"`` dispatches on real
    ``max_bpr``/padding/skew instead of dims-only zeros."""
    return _prepare_sparse_host(
        a, reorder=reorder, reorder_granularity=reorder_granularity,
        tau=tau, max_candidates=max_candidates, n_shards=n_shards)[1]


def prepare(a: bcsr_lib.BCSR, dtype=jnp.bfloat16, *,
            meta_only: bool = False, reorder: str = "identity",
            reorder_granularity: str = "element", tau: float = 0.7,
            max_candidates: Optional[int] = None, n_shards: int = 8):
    """Unified entry point for the local prepare twins (PR 8).

    ``meta_only=False`` (default) delegates to :func:`prepare_sparse` and
    returns ``(SparseArrays, SparseMeta)``; ``meta_only=True`` delegates
    to :func:`prepare_sparse_meta` and returns the ``SparseMeta`` alone
    (``dtype`` is ignored — meta is dtype-free by construction).  The
    twins stay as documented aliases; this is the name the package facade
    (``repro.prepare``) and the quickstart use.

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import ops
    >>> dense = np.kron(np.eye(4, dtype=np.float32), np.ones((8, 8)))
    >>> a = bcsr_lib.from_dense(dense, (8, 8))
    >>> arrays, meta = ops.prepare(a, dtype=jnp.float32)
    >>> ops.prepare(a, meta_only=True) == meta
    True
    """
    kw = dict(reorder=reorder, reorder_granularity=reorder_granularity,
              tau=tau, max_candidates=max_candidates, n_shards=n_shards)
    if meta_only:
        return prepare_sparse_meta(a, **kw)
    return prepare_sparse(a, dtype, **kw)


# the static ``auto`` pick (backend, bn) of a device-built structure, for
# both ops: its blocks change every step, so no fingerprint of them exists
# to look up
DEVICE_PICK = ("pallas", 512)


def device_sparse(row_ids: jnp.ndarray, col_ids: jnp.ndarray,
                  real_mask: jnp.ndarray, *, n_block_rows: int,
                  n_block_cols: int, block: Tuple[int, int],
                  max_bpr: int = 0) -> Tuple[SparseArrays, SparseMeta]:
    """A structure built on the device inside a traced step (as the
    dropless MoE builds its expert blocks), where ``prepare_sparse`` builds
    one on the host.  ``row_ids`` / ``col_ids`` are traced ``[nnzb]``
    int32 with ``row_ids`` sorted; ``nnzb`` is a static capacity whose
    unused entries carry ``real_mask`` False (``sddmm`` zeroes them, so
    ``spmm`` of the sampled blocks adds nothing for them).

    The transpose structure for the backward pass is built here too,
    with one sentinel entry (the zero block ``nnzb``) on every block-row
    of A^T, so the ``nnzb_t = nnzb + n_block_cols`` entries keep every
    A^T block-row nonempty whatever the routing.  ``vals`` is None: the
    caller puts the values in (``arrays._replace(vals=...)``).  The meta
    says ``built="device"``, which ``auto`` resolves to ``DEVICE_PICK``.
    """
    nnzb = int(row_ids.shape[0])
    if n_block_cols * (n_block_rows + 1) >= 2 ** 31:
        raise ValueError("structure too large for int32 transpose keys")
    t_row = jnp.concatenate([col_ids, jnp.arange(n_block_cols,
                                                 dtype=jnp.int32)])
    t_col = jnp.concatenate([row_ids, jnp.zeros((n_block_cols,),
                                                jnp.int32)])
    t_perm = jnp.concatenate([jnp.arange(nnzb, dtype=jnp.int32),
                              jnp.full((n_block_cols,), nnzb, jnp.int32)])
    # entries of a block-row of A^T in A^T column order, its sentinel last
    last = jnp.concatenate([t_col[:nnzb], jnp.full((n_block_cols,),
                                                   n_block_rows, jnp.int32)])
    order = jnp.argsort(t_row * (n_block_rows + 1) + last)
    arrays = SparseArrays(
        vals=None, row_ids=row_ids, col_ids=col_ids, real_mask=real_mask,
        t_perm=t_perm[order], t_row_ids=t_row[order],
        t_col_ids=t_col[order])
    h, w = block
    meta = SparseMeta(shape=(n_block_rows * h, n_block_cols * w),
                      block=tuple(block), n_block_rows=n_block_rows,
                      n_block_cols=n_block_cols, nnzb=nnzb,
                      nnzb_t=nnzb + n_block_cols, max_bpr=max_bpr,
                      built="device")
    return arrays, meta


# ------------------------------------------------------------ forward pieces
def _clamp_bn(bn: int, n: int) -> int:
    """Effective N-tile width: the configured bn, capped at N rounded up to
    the 128-lane width (a wider tile would only multiply padding).  This is
    what makes bn a real tuning dimension — the seed code clamped every bn
    to 128 (``min(cfg.bn, max(128, 1))``), so 256/512/1024 all ran the same
    grid."""
    return max(min(bn, -(-n // 128) * 128), 1)


def _pad_b(b: jnp.ndarray, w: int, bn: int):
    K, N = b.shape
    k_pad = (-K) % w
    n_pad = (-N) % bn
    if k_pad or n_pad:
        b = jnp.pad(b, ((0, k_pad), (0, n_pad)))
    return b, N


def _fwd_impl(cfg: SpmmConfig, meta: SparseMeta, arrays: SparseArrays,
              b: jnp.ndarray) -> jnp.ndarray:
    h, w = meta.block
    M, K = meta.shape
    out_dtype = jnp.dtype(cfg.out_dtype) if cfg.out_dtype else b.dtype
    bn = _clamp_bn(cfg.bn, b.shape[1])
    with jax.named_scope("smat.pad"):
        b_p, N = _pad_b(b, w, bn)
    bn = min(bn, b_p.shape[1])
    with jax.named_scope(f"smat.kernel.{cfg.backend}"):
        if cfg.backend == "pallas":
            out = pk.bcsr_spmm_nnz_stream(
                arrays.vals, arrays.row_ids, arrays.col_ids, b_p,
                meta.n_block_rows, bn=bn, out_dtype=out_dtype,
                interpret=cfg.interpret)
        elif cfg.backend == "xla":
            out = ref.bcsr_spmm_ref(arrays.vals, arrays.row_ids,
                                    arrays.col_ids, b_p, meta.n_block_rows,
                                    out_dtype=out_dtype)
        elif cfg.backend == "dense":
            dense = materialize_dense(arrays, meta)
            out = ref.spmm_dense_ref(dense, b_p[: dense.shape[1]],
                                     out_dtype=out_dtype)
        else:
            raise ValueError(f"unknown backend {cfg.backend!r}")
    with jax.named_scope("smat.epilogue"):
        out = out[:M, :N]
        if meta.reorder != "identity" and arrays.inv_perm is not None:
            # kernel computed C' = A' B in permuted row order; hand back
            # C = P^T C' so the permutation never leaks to callers
            out = jnp.take(out, arrays.inv_perm, axis=0)
    return out


def _dx_impl(cfg: SpmmConfig, meta: SparseMeta, arrays: SparseArrays,
             g: jnp.ndarray) -> jnp.ndarray:
    """dB = A^T @ dC via the transpose structure."""
    h, w = meta.block
    M, K = meta.shape
    with jax.named_scope("smat.permute"):
        sentinel = jnp.zeros((1,) + tuple(arrays.vals.shape[1:]),
                             dtype=arrays.vals.dtype)
        vals_ext = jnp.concatenate([arrays.vals, sentinel], axis=0)
        t_vals = jnp.transpose(vals_ext[arrays.t_perm], (0, 2, 1))
    bn = _clamp_bn(cfg.bn, g.shape[1])
    with jax.named_scope("smat.pad"):
        g_p, N = _pad_b(g, h, bn)
    bn = min(bn, g_p.shape[1])
    if cfg.backend == "pallas":
        with jax.named_scope("smat.kernel.pallas"):
            out = pk.bcsr_spmm_nnz_stream(
                t_vals, arrays.t_row_ids, arrays.t_col_ids, g_p,
                meta.n_block_cols, bn=bn, out_dtype=g.dtype,
                interpret=cfg.interpret)
    else:
        with jax.named_scope("smat.kernel.xla"):
            out = ref.bcsr_spmm_ref(t_vals, arrays.t_row_ids,
                                    arrays.t_col_ids, g_p, meta.n_block_cols,
                                    out_dtype=g.dtype)
    with jax.named_scope("smat.epilogue"):
        return out[:K, :N]


def _sddmm_impl(cfg: SpmmConfig, meta: SparseMeta, arrays: SparseArrays,
                x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """vals[s] = X'[block row_ids[s]] @ Y[block col_ids[s]]^T — the dense
    pair sampled at the stored structure (X' = P X when the structure was
    prepared with a reorder; callers pass X in ORIGINAL row order).

    Backends mirror the SpMM family: ``pallas`` streams the stored blocks
    by row panel, ``xla`` is the gather/einsum oracle, ``dense``
    materializes the full X @ Y^T and gathers blocks.  Padding entries
    (``real_mask`` False) are zeroed — they are structural, not
    values."""
    h, w = meta.block
    if meta.reorder != "identity" and arrays.row_perm is not None:
        with jax.named_scope("smat.permute"):
            x = jnp.take(x, arrays.row_perm, axis=0)
    out_dtype = jnp.dtype(cfg.out_dtype) if cfg.out_dtype else x.dtype
    # the streamed kernel contracts over all of N (or chunks of its own
    # choosing), so it pads N to whole lanes only; the oracles pad N to bn
    bn = 128 if cfg.backend == "pallas" else cfg.bn
    bn = _clamp_bn(bn, max(x.shape[1], y.shape[1]))
    with jax.named_scope("smat.pad"):
        x_p, _ = _pad_b(x, h, bn)
        y_p, _ = _pad_b(y, w, bn)
        n_pad = max(x_p.shape[1], y_p.shape[1])
        x_p = jnp.pad(x_p, ((0, 0), (0, n_pad - x_p.shape[1])))
        y_p = jnp.pad(y_p, ((0, 0), (0, n_pad - y_p.shape[1])))
    with jax.named_scope(f"smat.kernel.{cfg.backend}"):
        if cfg.backend == "pallas":
            vals = pk.bcsr_sddmm(x_p, y_p, arrays.row_ids, arrays.col_ids,
                                 h, w, out_dtype=out_dtype,
                                 interpret=cfg.interpret)
        elif cfg.backend == "xla":
            vals = ref.bcsr_sddmm_ref(x_p, y_p, arrays.row_ids,
                                      arrays.col_ids, h, w,
                                      out_dtype=out_dtype)
        elif cfg.backend == "dense":
            vals = ref.bcsr_sddmm_dense_ref(x_p, y_p, arrays.row_ids,
                                            arrays.col_ids, h, w,
                                            out_dtype=out_dtype)
        else:
            raise ValueError(f"unknown backend {cfg.backend!r}")
    # padding entries are structural zeros — never values, never gradients
    with jax.named_scope("smat.epilogue"):
        return vals * arrays.real_mask[:, None, None].astype(vals.dtype)


def materialize_dense(arrays: SparseArrays, meta: SparseMeta) -> jnp.ndarray:
    """Scatter the blocks into the padded dense matrix (cuBLAS arm)."""
    h, w = meta.block
    nbr, nbc = meta.n_block_rows, meta.n_block_cols
    flat = jnp.zeros((nbr * nbc, h, w), dtype=arrays.vals.dtype)
    flat = flat.at[arrays.row_ids * nbc + arrays.col_ids].add(arrays.vals)
    dense = flat.reshape(nbr, nbc, h, w).transpose(0, 2, 1, 3)
    return dense.reshape(nbr * h, nbc * w)


# ----------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmm(cfg: SpmmConfig, meta: SparseMeta, vals: jnp.ndarray,
          b: jnp.ndarray, rest: tuple) -> jnp.ndarray:
    arrays = SparseArrays(vals, *rest)
    return _fwd_impl(cfg, meta, arrays, b)


def _spmm_fwd(cfg, meta, vals, b, rest):
    arrays = SparseArrays(vals, *rest)
    return _fwd_impl(cfg, meta, arrays, b), (vals, b, rest)


def _spmm_bwd(cfg, meta, res, g):
    vals, b, rest = res
    arrays = SparseArrays(vals, *rest)
    g2 = g.astype(b.dtype)
    if meta.reorder != "identity" and arrays.row_perm is not None:
        # cotangent arrives in ORIGINAL row order; the stored structure is
        # A' = P A, so dB = A'^T (P dC) needs the permuted cotangent
        # g' = P g (the SDDMM op permutes its X operand itself)
        with jax.named_scope("smat.permute"):
            g2 = jnp.take(g2, arrays.row_perm, axis=0)
    db = _dx_impl(cfg, meta, arrays, g2)[: b.shape[0], : b.shape[1]]
    # dvals through the SDDMM op — SpMM and SDDMM are mutual duals, so
    # higher-order AD recurses between the two custom VJPs
    cfg_d = dataclasses.replace(cfg, out_dtype=str(vals.dtype))
    dvals = _sddmm(cfg_d, meta, g.astype(b.dtype), b, rest)
    zeros_rest = jax.tree.map(
        lambda x: np.zeros(x.shape, jax.dtypes.float0), rest)
    return dvals, db.astype(b.dtype), zeros_rest


_spmm.defvjp(_spmm_fwd, _spmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sddmm(cfg: SpmmConfig, meta: SparseMeta, x: jnp.ndarray,
           y: jnp.ndarray, rest: tuple) -> jnp.ndarray:
    arrays = SparseArrays(x, *rest)   # vals slot unused by the sampling
    return _sddmm_impl(cfg, meta, arrays, x, y)


def _sddmm_fwd(cfg, meta, x, y, rest):
    arrays = SparseArrays(x, *rest)
    return _sddmm_impl(cfg, meta, arrays, x, y), (x, y, rest)


def _sddmm_bwd(cfg, meta, res, g):
    x, y, rest = res
    real_mask = rest[2]
    gm = g * real_mask[:, None, None].astype(g.dtype)
    cfg_b = dataclasses.replace(cfg, out_dtype=None)
    # dX = G @ Y — exactly the SpMM forward on the cotangent blocks (the
    # op un-permutes back to original row order itself); dY = G^T @ X'
    # via the stored transpose structure, with X' = P X matching the
    # permuted sampling of the forward
    dx = _spmm(cfg_b, meta, gm.astype(y.dtype), y, rest)
    garr = SparseArrays(gm.astype(y.dtype), *rest)
    xp = x
    if meta.reorder != "identity" and garr.row_perm is not None:
        with jax.named_scope("smat.permute"):
            xp = jnp.take(x, garr.row_perm, axis=0)
    dy = _dx_impl(cfg_b, meta, garr, xp)[: y.shape[0], : y.shape[1]]
    zeros_rest = jax.tree.map(
        lambda t: np.zeros(t.shape, jax.dtypes.float0), rest)
    return dx.astype(x.dtype), dy.astype(y.dtype), zeros_rest


_sddmm.defvjp(_sddmm_fwd, _sddmm_bwd)


# ------------------------------------------------------------------ public API
def resolve_backend(backend: str, bn: int, meta: SparseMeta,
                    n: int, op: str = "spmm") -> Tuple[str, int]:
    """Normalize aliases and resolve ``auto`` through the variant registry.

    ``auto`` needs only static info (meta + N), so this is safe at trace
    time; a cache miss falls back to the analytic perf-model pick (timed
    sweeps only happen via explicit ``autotune.Autotuner.tune`` calls).
    ``op`` selects the variant family (``"spmm"`` | ``"sddmm"``) — the two
    share backend strings but fingerprint separately (v6 ``op=`` field),
    so an SpMM pick can never alias an SDDMM one.
    """
    if backend == "auto" and meta.built == "device":
        backend, bn = DEVICE_PICK
    elif backend == "auto":
        from repro.kernels import autotune  # local import: avoids cycle
        choice = autotune.get_autotuner().pick(meta, n, op=op)
        backend, bn = choice.backend, choice.bn
    backend = _BACKEND_ALIASES.get(backend, backend)
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of "
                         f"{BACKENDS + ('auto', 'nnz_stream')}")
    if os.environ.get("REPRO_VERIFY_LAUNCH") == "1":
        # opt-in pre-launch contract check: meta invariants, schedule
        # capacity, and the VMEM budget, all symbolic (repro.analysis)
        from repro.analysis import verify_launch as _verify_launch
        _verify_launch.assert_launch_ok(meta, backend, n=n, bn=bn, op=op)
    # host-side dispatch record (static info only, so trace-time safe —
    # same argument as the `auto` resolution above)
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    obs_trace.event("ops.dispatch", op=op, backend=backend, bn=bn, n=n,
                    nnzb=meta.nnzb, max_bpr=meta.max_bpr)
    obs_metrics.counter("ops.dispatch", op=op, backend=backend).inc()
    if op in ("spmm", "sddmm") and backend == "pallas":
        # stored blocks one grid step (one row panel) of the streamed
        # kernel takes, on average
        h, w = meta.block
        r = pk.panel_block_rows(h, meta.n_block_rows)
        obs_metrics.gauge(f"kernel.{op}.blocks_per_step", op=op).set(
            meta.nnzb / -(-meta.n_block_rows // r))
        if op == "sddmm":
            # launches over the contraction, N padded to whole lanes, on
            # bf16 operands (1: the panel's X held whole)
            _, n_chunks = pk.sddmm_chunks(h, w, -(-n // 128) * 128,
                                          meta.n_block_rows)
            obs_metrics.gauge("kernel.sddmm.k_chunks", op=op).set(n_chunks)
    return backend, bn


def spmm(arrays: SparseArrays, meta: SparseMeta, b: jnp.ndarray,
         *, backend: str = "pallas", bn: int = 512,
         interpret: bool = False, out_dtype=None) -> jnp.ndarray:
    """C = A @ B, differentiable w.r.t. ``arrays.vals`` and ``b``.

    A is the BCSR operand from ``prepare_sparse``; B is ``[K, N]`` dense.
    ``backend="auto"`` dispatches through the ``repro.kernels.autotune``
    registry using the matrix's stats fingerprint.  Outputs always come
    back in ORIGINAL row order, whatever ``reorder`` scheme prepared A.

    Example (sparse x dense against the dense oracle):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import ops
    >>> rng = np.random.default_rng(0)
    >>> dense = np.kron(rng.random((4, 4)) < 0.5,
    ...                 np.ones((8, 8))).astype(np.float32)
    >>> a = bcsr_lib.from_dense(dense, (8, 8))
    >>> arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    >>> b = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    >>> c = ops.spmm(arrays, meta, b, backend="xla")
    >>> c.shape
    (32, 16)
    >>> bool(jnp.allclose(c, dense @ np.asarray(b), atol=1e-5))
    True
    """
    backend, bn = resolve_backend(backend, bn, meta, int(b.shape[-1]))
    cfg = SpmmConfig(backend=backend, bn=bn, interpret=interpret,
                     out_dtype=str(jnp.dtype(out_dtype))
                     if out_dtype else None)
    rest = tuple(arrays[1:])
    return _spmm(cfg, meta, arrays.vals, b, rest)


def sddmm(arrays: SparseArrays, meta: SparseMeta, x: jnp.ndarray,
          y: jnp.ndarray, *, backend: str = "pallas", bn: int = 512,
          interpret: bool = False, out_dtype=None) -> jnp.ndarray:
    """Sampled dense-dense matmul: the blocks of ``X @ Y^T`` stored by the
    structure of ``(arrays, meta)`` — SpMM's dual, promoted from the SpMM
    VJP's private dW helper to a first-class op (the score kernel of
    block-sparse attention: ``Q K^T`` sampled on a BCSR mask).

    ``X`` is ``[M, N]`` (original row order — a reorder baked into the
    structure is applied internally, mirroring ``spmm``), ``Y`` is
    ``[K, N]``; the result is ``[nnzb, h, w]`` with padding entries
    (``real_mask`` False) zeroed.  Differentiable w.r.t. ``x`` and ``y``:
    dX runs as an SpMM of the cotangent blocks against ``Y``, dY as an
    SpMM through the stored transpose structure — the two ops are
    mutually recursive duals, so higher-order AD bounces between their
    custom VJPs (to any order on the pure-jnp ``xla`` backend; the
    Pallas leaf kernels have no JVP rule, capping the order there).
    ``backend="auto"`` resolves through the
    ``repro.kernels.autotune`` SDDMM variant family (v6 ``op=sddmm``
    fingerprints — never aliased with SpMM picks).

    Example (sampled product vs the dense masked oracle):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import ops
    >>> rng = np.random.default_rng(0)
    >>> dense = np.kron(rng.random((4, 4)) < 0.5,
    ...                 np.ones((8, 8))).astype(np.float32)
    >>> a = bcsr_lib.from_dense(dense, (8, 8))
    >>> arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    >>> x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    >>> y = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    >>> vals = ops.sddmm(arrays, meta, x, y, backend="xla")
    >>> vals.shape == (meta.nnzb, 8, 8)
    True
    >>> full = np.asarray(x) @ np.asarray(y).T   # dense X Y^T, then sample
    >>> blk = full.reshape(4, 8, 4, 8).transpose(0, 2, 1, 3)[
    ...     np.asarray(arrays.row_ids), np.asarray(arrays.col_ids)]
    >>> blk *= np.asarray(arrays.real_mask)[:, None, None]  # padding -> 0
    >>> bool(jnp.allclose(vals, blk, atol=1e-4))
    True
    """
    backend, bn = resolve_backend(backend, bn, meta, int(x.shape[-1]),
                                  op="sddmm")
    cfg = SpmmConfig(backend=backend, bn=bn, interpret=interpret,
                     out_dtype=str(jnp.dtype(out_dtype))
                     if out_dtype else None)
    rest = tuple(arrays[1:])
    return _sddmm(cfg, meta, x, y, rest)

