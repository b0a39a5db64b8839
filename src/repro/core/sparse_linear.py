"""BlockSparseLinear — the paper's technique as a first-class model layer.

A linear layer ``y = x @ W^T (+ b)`` whose weight ``W [out, in]`` is stored in
BCSR and multiplied with the SMaT kernels: the forward pass is
``C = W @ x^T`` (sparse x dense SpMM), the backward pass uses the transposed
block structure (dx) and the SDDMM kernel (dW) — all through
``kernels.ops.spmm``'s custom VJP.

Patterns are generated with exact nnzb and full row/col coverage so layers
can be stacked along a scan axis (all leaves share shapes).  Patterns are
STRUCTURAL and deterministic in a python-int seed, which is what makes the
static structure-metadata pipeline work: ``sparse_linear_meta`` re-derives
the exact init-time meta (true ``max_bpr``/padding/skew stats, per-shard
``ShardedMeta``) from ``(seed, dims, spec)`` alone — no params needed — so
the model apply path (``models.layers.mlp``) dispatches on real structure
stats while the stats ride as hashable STATIC aux data, never as pytree
leaves (see ``docs/ARCHITECTURE.md``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcsr as bcsr_lib
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class SparsitySpec:
    """Config for a block-sparse weight (the paper's technique toggle).

    ``backend="auto"`` routes every apply through the
    ``repro.kernels.autotune`` registry: the variant and N-tile are picked
    from the weight's structure fingerprint (cached analytic pick unless a
    measured sweep already ran).  ``tune_n > 0`` additionally runs the
    timed micro-sweep once at ``init_sparse_linear`` time with ``N =
    tune_n`` — set it to the expected activation token count (batch x seq
    of a training/serving step) so the warmed cache bucket is the one
    apply-time lookups actually hit.

    ``reorder`` applies a block-row permutation to the weight at init
    (``core.permute.SCHEMES``: jaccard | rcm | shard_balance | identity).
    Block-row granularity keeps nnzb static, so scan-stacked layers keep
    sharing leaf shapes; ``ops.spmm`` un-permutes outputs, so the layer's
    math is unchanged.  ``shard_balance`` balances per-shard nonzero-block
    loads over ``reorder_shards`` shards (0 = derive from the runtime
    device count via ``launch.sharding.spmm_shard_count``).

    ``shards > 0`` (or ``shards="auto"``) switches the layer to the
    PARTITIONED execution path (``launch.dist_spmm``): the weight is
    split over block-rows into load-balanced slices with static per-shard
    schedules, each shard resolves its own kernel variant from its REAL
    structure stats (the per-shard ``SparseMeta`` inside the returned
    ``ShardedMeta``), and the apply runs as a ``shard_map`` when a
    compatible mesh is active (``dist_spmm.use_spmm_mesh``) or as the
    in-process equivalent otherwise.  ``shards="auto"`` resolves the
    shard count through the autotuner's shard-count axis
    (``resolved_shards`` — a DIMS-ONLY pseudo meta feeds
    ``Autotuner.pick_shards``, so scan-stacked layers sharing this spec
    resolve the same S and keep identical leaf shapes).  Per-shard slice
    shapes are derived from the layer dims alone (``shard_shapes``), so
    scan-stacked layers with different structures still share every leaf
    shape.  ``shard_cols`` adds the optional 2D column split over the
    activation panel; ``shard_chunks`` sets the overlap pipeline depth
    the sharded apply runs with (``spmm_sharded(n_chunks=...)`` — chunked
    execution is bit-identical to single-shot, so the default is on).

    Example — a partitioned block-sparse layer, applied and then
    re-derived statically (no params) via ``sparse_linear_meta``:

    >>> import jax.numpy as jnp
    >>> from repro.core.sparse_linear import (SparsitySpec,
    ...     apply_sparse_linear, init_sparse_linear, sparse_linear_meta)
    >>> spec = SparsitySpec(density=0.3, block=(16, 16), backend="auto",
    ...                     shards=2)
    >>> params, meta = init_sparse_linear(0, 64, 96, spec,
    ...                                   dtype=jnp.float32)
    >>> (meta.n_shards, all(m.max_bpr > 0 for m in meta.shard_metas))
    (2, True)
    >>> x = jnp.ones((2, 3, 64), jnp.float32)
    >>> apply_sparse_linear(params, meta, x, spec).shape
    (2, 3, 96)
    >>> sparse_linear_meta(0, 64, 96, spec) == meta    # static re-derivation
    True
    """
    density: float = 0.1            # fraction of nonzero blocks
    block: Tuple[int, int] = (128, 128)
    backend: str = "pallas"         # pallas | xla | dense | auto
    bn: int = 512
    interpret: bool = False
    tune_n: int = 0                 # measured sweep at init for this N
    reorder: str = "identity"       # weight row-permutation scheme
    reorder_shards: int = 0         # shard_balance bins (0 = auto)
    shards: object = 0              # >0 | "auto": partitioned execution
    shard_cols: int = 1             # optional column split over activations
    shard_chunks: int = 2           # overlap pipeline depth (sharded path)


def is_sharded(spec: SparsitySpec) -> bool:
    """True when the spec selects the partitioned execution path — an
    explicit shard count OR the ``"auto"`` sentinel (which may still
    resolve to S=1; the layer then runs the sharded code path with one
    shard, keeping leaf layouts uniform across a spec)."""
    return spec.shards == "auto" or \
        (isinstance(spec.shards, int) and spec.shards > 0)


def resolved_shards(spec: SparsitySpec, out_dim: int, in_dim: int,
                    max_shards: Optional[int] = None) -> int:
    """The spec's effective shard count for a layer of these dims.

    Explicit ``shards=N`` passes through; ``shards="auto"`` asks the
    autotuner's shard-count axis (``Autotuner.pick_shards``) with a
    DIMS-ONLY pseudo meta — the same ``_nnzb_for`` budget the leaf shapes
    use, deliberately NOT any one layer's drawn structure, so every
    scan-stacked layer sharing the spec resolves the same S and the leaf
    shapes stay shared.  ``max_shards`` defaults to the runtime mesh/
    device size (``launch.sharding.spmm_shard_count``); the resolution is
    deterministic in (dims, spec, max_shards) and cached under the v7
    ``shards|...`` key."""
    if not is_sharded(spec):
        return 0
    if spec.shards != "auto":
        return int(spec.shards)
    from repro.kernels import autotune
    h, w = spec.block
    nbr, nbc = -(-out_dim // h), -(-in_dim // w)
    nnzb = _nnzb_for(spec, out_dim, in_dim)
    pseudo = ops.SparseMeta(
        shape=(out_dim, in_dim), block=spec.block, n_block_rows=nbr,
        n_block_cols=nbc, nnzb=nnzb, nnzb_t=nnzb, reorder=spec.reorder)
    if max_shards is None:
        from repro.launch.sharding import spmm_shard_count  # local: layering
        max_shards = max(spmm_shard_count(), 1)
    choice = autotune.get_autotuner().pick_shards(
        pseudo, spec.tune_n or 512, max_shards=max_shards,
        n_chunks=max(spec.shard_chunks, 1))
    return choice.n_shards


def _nnzb_for(spec: SparsitySpec, out_dim: int, in_dim: int) -> int:
    h, w = spec.block
    nbr, nbc = -(-out_dim // h), -(-in_dim // w)
    nnzb = int(round(spec.density * nbr * nbc))
    nnzb = max(nnzb, max(nbr, nbc))
    # round up to a multiple of 16 so the nnz dimension shards over the
    # `model` mesh axis (dropped to the cap when the matrix is tiny)
    nnzb = min(-(-nnzb // 16) * 16, nbr * nbc)
    return nnzb


def _reorder_shards(spec: SparsitySpec) -> int:
    if spec.reorder_shards:
        return spec.reorder_shards
    from repro.launch.sharding import spmm_shard_count  # local: layering
    return spmm_shard_count()


def shard_shapes(spec: SparsitySpec, out_dim: int, in_dim: int,
                 n_shards: Optional[int] = None):
    """Dims-only per-shard static sizes: (rows_per_shard, nnzb_per_shard,
    nnzb_t_per_shard).

    Scan-stacked layers share one spec but draw different structures, so
    the per-shard budgets CANNOT depend on any one layer's LPT outcome.
    The entry budget is the balanced average plus 25% skew headroom (and a
    small-case floor) plus one slot per row for virtual-row sentinels;
    ``prepare_sharded`` raises if a structure is too skewed to fit, which
    for the near-uniform ``random_bcsr_exact`` patterns does not happen.
    ``n_shards`` overrides the spec's count (the resolved value when
    ``spec.shards="auto"``)."""
    h, w = spec.block
    S = n_shards if n_shards is not None \
        else resolved_shards(spec, out_dim, in_dim)
    nbr, nbc = -(-out_dim // h), -(-in_dim // w)
    nnzb = _nnzb_for(spec, out_dim, in_dim)
    rps = -(-nbr // S)
    eff = min(S, nbr)
    avg = -(-nnzb // eff)
    nnzb_ps = min(nnzb + rps, avg + max(avg // 4, 8) + rps)
    return rps, nnzb_ps, nnzb_ps + nbc


def _pattern_for(seed: int, in_dim: int, out_dim: int,
                 spec: SparsitySpec) -> bcsr_lib.BCSR:
    """THE weight pattern of ``(seed, dims, spec)`` — single construction
    site shared by ``init_sparse_linear`` (arrays + meta) and
    ``sparse_linear_meta`` (meta only), so the two derivations can never
    drift apart."""
    return bcsr_lib.random_bcsr_exact(
        seed, (out_dim, in_dim), spec.block,
        _nnzb_for(spec, out_dim, in_dim), dtype=np.float32)


@functools.lru_cache(maxsize=None)
def sparse_linear_meta(seed: int, in_dim: int, out_dim: int,
                       spec: SparsitySpec):
    """True structure meta of the layer ``init_sparse_linear(seed, ...)``
    builds, derived WITHOUT allocating params — memoized host work.

    Patterns are deterministic in the python-int ``seed``, so the meta
    (real ``max_bpr`` / padding / skew stats after the spec's ``reorder``;
    the full per-shard ``ShardedMeta`` when ``spec.shards > 0``) is a pure
    static function of ``(seed, dims, spec)``.  The model path uses this
    at trace time (``models.layers.mlp``) so ``backend="auto"`` resolves
    heterogeneous per-shard kernel picks from the permuted structure —
    identically to dispatching on the meta ``init_sparse_linear``
    returned."""
    a = _pattern_for(seed, in_dim, out_dim, spec)
    if is_sharded(spec):
        from repro.launch import dist_spmm  # local: layering
        S = resolved_shards(spec, out_dim, in_dim)
        rps, nnzb_ps, _ = shard_shapes(spec, out_dim, in_dim, n_shards=S)
        return dist_spmm.prepare_sharded_meta(
            a, S, col_shards=spec.shard_cols,
            reorder=spec.reorder, rows_per_shard=rps,
            nnzb_per_shard=nnzb_ps)
    return ops.prepare_sparse_meta(
        a, reorder=spec.reorder, reorder_granularity="block_row",
        n_shards=_reorder_shards(spec))


def _merge_two(m0: ops.SparseMeta, m1: ops.SparseMeta) -> ops.SparseMeta:
    static0 = dataclasses.replace(m0, max_bpr=0, padding_ratio_pct=0,
                                  bpr_cv_pct=0)
    static1 = dataclasses.replace(m1, max_bpr=0, padding_ratio_pct=0,
                                  bpr_cv_pct=0)
    if static0 != static1:
        raise ValueError(
            f"cannot merge metas with different static structure:\n"
            f"  {static0}\n  {static1}")
    return dataclasses.replace(
        m0, max_bpr=max(m0.max_bpr, m1.max_bpr),
        padding_ratio_pct=max(m0.padding_ratio_pct, m1.padding_ratio_pct),
        bpr_cv_pct=max(m0.bpr_cv_pct, m1.bpr_cv_pct))


def merge_sparse_metas(metas):
    """Conservative merge of per-layer structure metas into ONE stack meta.

    Scan-stacked layers share a spec (identical shapes / nnzb / budgets)
    but draw different structures; the scanned body traces once, so it
    must dispatch on a single static meta.  The merge keeps the shared
    static fields and takes the elementwise MAX of the stats
    (``max_bpr``, padding and skew); for ``ShardedMeta`` the per-shard
    metas merge shard-wise, preserving cross-shard heterogeneity.  Raises
    if the metas' static structure differs (different specs were
    mixed)."""
    metas = list(metas)
    if not metas:
        raise ValueError("merge_sparse_metas needs at least one meta")
    first = metas[0]
    if isinstance(first, ops.SparseMeta):
        out = first
        for m in metas[1:]:
            out = _merge_two(out, m)
        return out
    # ShardedMeta: merge shard-wise (lazy import keeps core -> launch
    # layering one-directional at module load)
    from repro.launch import dist_spmm  # local: layering
    if not isinstance(first, dist_spmm.ShardedMeta):
        raise TypeError(f"unknown meta type {type(first).__name__}")
    for m in metas[1:]:
        if dataclasses.replace(m, shard_metas=()) != \
                dataclasses.replace(first, shard_metas=()):
            raise ValueError(
                "cannot merge ShardedMetas with different static structure")
    shard_metas = tuple(
        functools.reduce(_merge_two, [m.shard_metas[s] for m in metas])
        for s in range(first.n_shards))
    return dataclasses.replace(first, shard_metas=shard_metas)


def init_sparse_linear(key: int, in_dim: int, out_dim: int,
                       spec: SparsitySpec, dtype=jnp.bfloat16):
    """Returns (params, meta): params is a pytree of device arrays (vals is
    the trainable leaf; index arrays — including the ``reorder`` row
    permutation — ride along), meta is static.

    With ``spec.shards > 0`` the params carry the row-partitioned index
    structure from ``launch.dist_spmm.prepare_sharded`` instead (``vals``
    stays the flat trainable leaf) and ``meta`` is a ``ShardedMeta``.

    The returned meta carries the layer's TRUE structure stats and is
    reproducible without params: ``sparse_linear_meta(key, in_dim,
    out_dim, spec)`` returns an equal meta (the specs-vs-init contract
    ``tests/test_static_meta.py`` pins)."""
    a = _pattern_for(key, in_dim, out_dim, spec)
    if is_sharded(spec):
        from repro.launch import dist_spmm  # local: layering
        S = resolved_shards(spec, out_dim, in_dim)
        rps, nnzb_ps, _ = shard_shapes(spec, out_dim, in_dim, n_shards=S)
        sharr, smeta = dist_spmm.prepare_sharded(
            a, S, col_shards=spec.shard_cols, dtype=dtype,
            reorder=spec.reorder, rows_per_shard=rps,
            nnzb_per_shard=nnzb_ps)
        if spec.backend == "auto" and spec.tune_n > 0:
            # sharded analogue of the unsharded tune() below: measured
            # winners land under each shard's v7 fingerprint
            dist_spmm.tune_shards(sharr, smeta, spec.tune_n,
                                  interpret=spec.interpret)
        params = {
            "vals": sharr.vals,
            "shard_src": sharr.src_index,
            "shard_row_ids": sharr.row_ids,
            "shard_col_ids": sharr.col_ids,
            "shard_mask": sharr.real_mask,
            "shard_t_perm": sharr.t_perm,
            "shard_t_row_ids": sharr.t_row_ids,
            "shard_t_col_ids": sharr.t_col_ids,
            "gather_rows": sharr.gather_rows,
        }
        return params, smeta
    n_shards = _reorder_shards(spec)
    # block_row granularity: the permutation relabels whole block-rows, so
    # nnzb (and every leaf shape) matches sparse_linear_specs exactly
    arrays, meta = ops.prepare_sparse(
        a, dtype=dtype, reorder=spec.reorder,
        reorder_granularity="block_row", n_shards=n_shards)
    if spec.backend == "auto" and spec.tune_n > 0:
        from repro.kernels import autotune
        autotune.get_autotuner().tune(
            a, spec.tune_n, interpret=spec.interpret, reorder=spec.reorder,
            reorder_granularity="block_row", n_shards=n_shards)
    params = {
        "vals": arrays.vals,
        "row_ids": arrays.row_ids,
        "col_ids": arrays.col_ids,
        "real_mask": arrays.real_mask,
        "t_perm": arrays.t_perm,
        "t_row_ids": arrays.t_row_ids,
        "t_col_ids": arrays.t_col_ids,
        "row_perm": arrays.row_perm,
        "inv_perm": arrays.inv_perm,
    }
    return params, meta


def sparse_linear_specs(in_dim: int, out_dim: int, spec: SparsitySpec,
                        dtype=jnp.bfloat16, seed: Optional[int] = None):
    """ShapeDtypeStruct pytree for the layer (dry-run / scan planning).

    With ``spec.shards > 0`` the specs mirror the partitioned layout of
    ``init_sparse_linear`` exactly — every per-shard size comes from
    ``shard_shapes`` (dims only), so specs and real params always agree.

    ``seed`` controls the returned META's stats.  With the layer's actual
    init seed, the meta is the TRUE structure meta (``sparse_linear_meta``
    — real per-shard stats, real post-reorder ``max_bpr``), equal to what
    ``init_sparse_linear(seed, ...)`` returns; the params stay
    ShapeDtypeStructs either way.  With ``seed=None`` (pure dims-only
    mode, no host work at all) the stats are zero, so ``auto`` dispatch
    sees dims only — fine for shape/sharding proofs, wrong for
    kernel-choice questions."""
    if seed is not None:
        params, _ = sparse_linear_specs(in_dim, out_dim, spec, dtype)
        return params, sparse_linear_meta(seed, in_dim, out_dim, spec)
    h, w = spec.block
    nnzb = _nnzb_for(spec, out_dim, in_dim)
    nbr, nbc = -(-out_dim // h), -(-in_dim // w)
    sds = jax.ShapeDtypeStruct
    if is_sharded(spec):
        from repro.launch import dist_spmm  # local: layering
        S = resolved_shards(spec, out_dim, in_dim)
        rps, nnzb_ps, nnzb_t_ps = shard_shapes(spec, out_dim, in_dim,
                                               n_shards=S)
        params = {
            "vals": sds((nnzb, h, w), dtype),
            "shard_src": sds((S, nnzb_ps), jnp.int32),
            "shard_row_ids": sds((S, nnzb_ps), jnp.int32),
            "shard_col_ids": sds((S, nnzb_ps), jnp.int32),
            "shard_mask": sds((S, nnzb_ps), jnp.bool_),
            "shard_t_perm": sds((S, nnzb_t_ps), jnp.int32),
            "shard_t_row_ids": sds((S, nnzb_t_ps), jnp.int32),
            "shard_t_col_ids": sds((S, nnzb_t_ps), jnp.int32),
            "gather_rows": sds((out_dim,), jnp.int32),
        }
        shard_meta = ops.SparseMeta(
            shape=(rps * h, in_dim), block=spec.block, n_block_rows=rps,
            n_block_cols=nbc, nnzb=nnzb_ps, nnzb_t=nnzb_t_ps,
            reorder="identity", n_shards=S)
        meta = dist_spmm.ShardedMeta(
            shape=(out_dim, in_dim), block=spec.block, n_shards=S,
            col_shards=spec.shard_cols, rows_per_shard=rps, nnzb=nnzb,
            nnzb_per_shard=nnzb_ps, nnzb_t_per_shard=nnzb_t_ps,
            shard_metas=(shard_meta,) * S, reorder=spec.reorder)
        return params, meta
    params = {
        "vals": sds((nnzb, h, w), dtype),
        "row_ids": sds((nnzb,), jnp.int32),
        "col_ids": sds((nnzb,), jnp.int32),
        "real_mask": sds((nnzb,), jnp.bool_),
        "t_perm": sds((nnzb,), jnp.int32),
        "t_row_ids": sds((nnzb,), jnp.int32),
        "t_col_ids": sds((nnzb,), jnp.int32),
        "row_perm": sds((out_dim,), jnp.int32),
        "inv_perm": sds((out_dim,), jnp.int32),
    }
    meta = ops.SparseMeta(shape=(out_dim, in_dim), block=spec.block,
                          n_block_rows=nbr, n_block_cols=nbc,
                          nnzb=nnzb, nnzb_t=nnzb, reorder=spec.reorder)
    return params, meta


def shard_balance_report(in_dim: int, out_dim: int, spec: SparsitySpec,
                         seed: int = 7919) -> dict:
    """Per-shard nnzb balance of the layer this spec + seed would build
    (host-only; the dry-run prints it so the partition quality is visible
    before any launch)."""
    from repro.launch import dist_spmm  # local: layering
    a = _pattern_for(seed, in_dim, out_dim, spec)
    S = resolved_shards(spec, out_dim, in_dim)
    rps, _, _ = shard_shapes(spec, out_dim, in_dim, n_shards=S)
    return dist_spmm.shard_balance_stats(a, S, rows_per_shard=rps)


def apply_sparse_linear(params: dict, meta, x: jnp.ndarray,
                        spec: SparsitySpec) -> jnp.ndarray:
    """y[..., out] = x[..., in] @ W^T via C = W @ x^T.

    Unsharded: the token dim of the SpMM is sharded over ALL mesh axes
    (weights are replicated — see launch/sharding.py BCSR rules): each
    chip streams the full nonzero-block list against its token slice,
    which is exactly the paper's kernel with B = the local activation
    panel (§Perf C2).

    Sharded (``meta`` is a ``ShardedMeta``): the weight's block-rows are
    partitioned instead — each shard streams only its balanced slice, as
    a ``shard_map`` over the mesh installed by ``dist_spmm.use_spmm_mesh``
    (in-process equivalent when none is), with the activation panel
    pipelined in ``spec.shard_chunks`` overlapped column chunks
    (bit-identical to single-shot; see ``dist_spmm.spmm_sharded``)."""
    from repro.launch.constrain import BATCH, MODEL, constrain
    lead = x.shape[:-1]
    in_dim = x.shape[-1]
    xt = x.reshape(-1, in_dim).T                     # [K, T]
    if is_sharded(spec):
        from repro.launch import dist_spmm  # local: layering
        sharr = dist_spmm.ShardedArrays(
            vals=params["vals"], src_index=params["shard_src"],
            row_ids=params["shard_row_ids"], col_ids=params["shard_col_ids"],
            real_mask=params["shard_mask"], t_perm=params["shard_t_perm"],
            t_row_ids=params["shard_t_row_ids"],
            t_col_ids=params["shard_t_col_ids"],
            gather_rows=params["gather_rows"])
        mesh = dist_spmm.current_spmm_mesh()
        if mesh is None:
            # in-process fallback under a TRAINING mesh: keep the token
            # panel sharded over all ambient axes, exactly like the
            # unsharded path (each chip runs every slice against its own
            # token slice)
            xt = constrain(xt, None, BATCH + (MODEL,))
        c = dist_spmm.spmm_sharded(
            sharr, meta, xt, backend=spec.backend, bn=spec.bn,
            interpret=spec.interpret, mesh=mesh,
            n_chunks=max(spec.shard_chunks, 1))
        if mesh is None:
            c = constrain(c, None, BATCH + (MODEL,))
        return c.T.reshape(*lead, meta.shape[0])
    arrays = ops.SparseArrays(
        vals=params["vals"], row_ids=params["row_ids"],
        col_ids=params["col_ids"], real_mask=params["real_mask"],
        t_perm=params["t_perm"], t_row_ids=params["t_row_ids"],
        t_col_ids=params["t_col_ids"],
        row_perm=params.get("row_perm"), inv_perm=params.get("inv_perm"))
    xt = constrain(xt, None, BATCH + (MODEL,))       # tokens over all axes
    c = ops.spmm(arrays, meta, xt, backend=spec.backend, bn=spec.bn,
                 interpret=spec.interpret)           # [M, T]
    c = constrain(c, None, BATCH + (MODEL,))
    return c.T.reshape(*lead, meta.shape[0])


def sparse_param_flops(meta: ops.SparseMeta) -> int:
    """FLOPs per token of this layer (2 * nnzb * h * w) — used by the
    roofline's MODEL_FLOPS accounting for sparse archs."""
    h, w = meta.block
    return 2 * meta.nnzb * h * w
