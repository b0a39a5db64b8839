"""Sharded SpMM execution: row-partitioned BCSR over a device mesh.

SMaT's single-device wins only reach the serving north star if the SpMM
scales past one chip.  This module turns the reorder pipeline's dormant
``shard_balance`` scheme into a working scaling axis:

  * ``prepare_sharded`` partitions a host BCSR over block-rows (1D) using
    the capacitated LPT bin assignment from ``core.permute.shard_bins``:
    every shard owns exactly ``rows_per_shard`` block-row slots (trailing
    slots virtual/empty) and a fixed ``nnzb_per_shard`` entry budget, so
    the per-shard schedules are STATIC — scan/jit shapes never depend on
    which shard a block landed in.  Per-shard nonzero-block loads come out
    near-equal (the paper's mip1 observation, lifted from warps to
    devices; Acc-SpMM makes the same point for TC pipelines).
  * ``spmm_sharded`` executes the partition either as a ``shard_map`` over
    a dedicated mesh axis (real multi-device execution; the column split
    over B adds an optional 2D axis) or as an in-process "local" loop with
    identical math (the fallback when no compatible mesh exists — unit
    tests, single-chip serving).  Each shard resolves its OWN kernel
    variant through ``ops.resolve_backend``: per-shard metas carry
    ``n_shards`` into the v7 autotune fingerprint, and shards whose picks
    differ dispatch through a ``lax.switch`` on the mesh axis index.
  * ``spmm_sharded(n_chunks=K)`` pipelines the B operand movement against
    shard compute (Acc-SpMM's overlap, lifted to the collective level):
    the panel is cut into K ascending column chunks over the ``spmm_col``
    axis and the staging of chunk k+1 is ISSUED before the matmul over
    chunk k (``lax.optimization_barrier`` pins the issue order; XLA's
    async copy/collective engine runs the movement under the compute).
    Column panels of a matmul are independent, so the chunked result is
    BIT-IDENTICAL to the unchunked one — fixed ascending chunk order,
    same per-column accumulation tree, and kernel picks resolved at the
    full panel width (``tests/test_sharded_properties.py`` pins this).
  * Shard count is an AUTOTUNE AXIS: ``prepare_sharded(a, "auto")``
    resolves S through ``Autotuner.pick_shards`` (analytic pipeline
    model over {1,2,4,8}, cached under ``shards|max=<M>|<v7 nk= key>``),
    and ``tune_shard_count`` runs the timed S micro-sweep.
  * Extreme single-row skew is handled by ENTRY-GRANULAR SPLITS
    (``split_heavy_rows=True``): a block-row heavier than the balanced
    per-shard budget splits into contiguous entry fragments placed by the
    same LPT (``core.permute.split_heavy_rows``), and the row's partial
    sums recombine with a scatter-add at gather time.  Without splits, a
    structure whose derived budget would silently over-allocate (every
    shard padded to one dominant row's size) now raises instead.
  * Results gather back to ORIGINAL row order (``gather_rows`` composes
    the optional pre-reorder with the partition permutation), so the
    sharding — like the PR 2 reorder — never leaks to callers; gradients
    flow through the inner per-shard ``ops.spmm`` custom VJP, the
    ``shard_map`` transpose (partial dB psums across shards), and the
    outer gather's transpose (padding rows receive exact zeros).

Wired end-to-end via ``SparsitySpec(shards=...)`` (``shards="auto"``
resolves through the same pick) -> ``init_sparse_linear`` ->
``apply_sparse_linear`` (which reads the ambient mesh from
``use_spmm_mesh`` and the overlap depth from ``spec.shard_chunks``) ->
the serve engine's decode path; ``launch.dryrun`` reports the per-shard
nnzb balance, resolved S, and chunk schedule of sparse layers.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core import bcsr as bcsr_lib
from repro.core import permute as permute_lib
from repro.kernels import ops
from repro.launch import mesh as mesh_lib
from repro.obs import jaxmon
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

AXIS_ROW = "spmm"        # mesh axis the block-row partition maps onto
AXIS_COL = "spmm_col"    # optional 2D axis: column split over B


# ---------------------------------------------------------------------- types
class ShardedArrays(NamedTuple):
    """Device arrays of a row-partitioned BCSR operand (pytree leaves).

    ``vals`` stays the FLAT global entry list — the single trainable leaf,
    shaped exactly like the unsharded operand's so parameter trees,
    optimizers, and sharding rules are unchanged.  The per-shard leaves
    are index structure only (leading axis = shard):

      src_index  [S, nnzb_ps]    entry index into vals (nnzb = zero sentinel)
      row_ids    [S, nnzb_ps]    LOCAL block-row ids, sorted row-major
      col_ids    [S, nnzb_ps]    global block-col ids
      real_mask  [S, nnzb_ps]    False for sentinel/padding entries
      t_perm     [S, nnzb_t_ps]  local transpose gather (nnzb_ps = sentinel)
      t_row_ids  [S, nnzb_t_ps]  block-rows of the local A^T (= global bcols)
      t_col_ids  [S, nnzb_t_ps]  LOCAL block-rows of A
      gather_rows [M]            original row -> row of the stacked shard
                                 outputs (composes pre-reorder + partition)
      split_src   [n_extra]      stacked-output rows of NON-PRIMARY row
                                 fragments (entry-granular splits); empty
                                 (0,) when no block-row was split
      split_dst   [n_extra]      original rows those partial sums add into
                                 (``out.at[split_dst].add(out_pad[split_src])``)
    """
    vals: jnp.ndarray
    src_index: jnp.ndarray
    row_ids: jnp.ndarray
    col_ids: jnp.ndarray
    real_mask: jnp.ndarray
    t_perm: jnp.ndarray
    t_row_ids: jnp.ndarray
    t_col_ids: jnp.ndarray
    gather_rows: jnp.ndarray
    split_src: Optional[jnp.ndarray] = None
    split_dst: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class ShardedMeta:
    """Static (hashable) metadata of a sharded operand.

    ``shard_metas[s]`` is a full per-shard ``SparseMeta`` (shape
    ``(rows_per_shard*h, K)``, ``nnzb = nnzb_per_shard``, its own
    max_bpr/padding/skew stats, ``n_shards`` set) — the fingerprint the
    autotuner picks each shard's kernel variant from."""
    shape: Tuple[int, int]              # logical global (M, K)
    block: Tuple[int, int]
    n_shards: int
    col_shards: int
    rows_per_shard: int                 # block-row slots per shard
    nnzb: int                           # global flat entry count (vals leaf)
    nnzb_per_shard: int
    nnzb_t_per_shard: int
    shard_metas: Tuple[ops.SparseMeta, ...]
    reorder: str = "identity"           # pre-partition scheme (reporting)
    n_split_fragments: int = 0          # extra (non-primary) row fragments


# ------------------------------------------------------------- ambient mesh
_MESH_STACK: list = [None]


@contextlib.contextmanager
def use_spmm_mesh(mesh):
    """Route ``apply_sparse_linear``'s sharded path through ``mesh`` for the
    duration (trace-time setting: the mesh is baked into the jitted program
    traced inside).  ``mesh=None`` is a no-op passthrough."""
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def current_spmm_mesh():
    return _MESH_STACK[-1]


def make_spmm_mesh(n_shards: int, col_shards: int = 1):
    """Dedicated (n_shards,) or (n_shards, col_shards) mesh over the first
    local devices, axes ``(AXIS_ROW[, AXIS_COL])``."""
    need = n_shards * col_shards
    if jax.device_count() < need:
        raise ValueError(
            f"spmm mesh needs {need} devices, have {jax.device_count()} "
            "(CPU testing: XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    if col_shards > 1:
        return mesh_lib.make_mesh((n_shards, col_shards), (AXIS_ROW, AXIS_COL))
    return mesh_lib.make_mesh((n_shards,), (AXIS_ROW,))


# ----------------------------------------------------------------- chunking
def chunk_schedule(n: int, n_chunks: int) -> Tuple[Tuple[int, int], ...]:
    """Ascending ``(start, stop)`` column chunks that partition ``[0, n)``.

    The schedule is the overlap pipeline's static contract: chunks are
    contiguous, strictly ascending, non-empty, and cover every column
    exactly once (``analysis.verify_launch.verify_chunk_schedule`` checks
    these invariants over the structure zoo).  ``n_chunks`` is clamped to
    ``n`` so tiny panels never produce empty chunks.

    >>> chunk_schedule(10, 4)
    ((0, 3), (3, 6), (6, 9), (9, 10))
    >>> chunk_schedule(8, 1)
    ((0, 8),)
    """
    if n < 1:
        raise ValueError(f"panel width must be >= 1, got {n}")
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    k = min(int(n_chunks), int(n))
    width = -(-n // k)
    bounds = []
    start = 0
    while start < n:
        stop = min(start + width, n)
        bounds.append((start, stop))
        start = stop
    return tuple(bounds)


@jax.custom_vjp
def _stage(x: jnp.ndarray) -> jnp.ndarray:
    """Pin the ISSUE point of a chunk's operand movement.

    ``optimization_barrier`` keeps XLA from sinking the staging of chunk
    k+1 below the matmul over chunk k, so the async copy/collective
    engine can run the movement under the compute.  Value-identity: the
    barrier never changes bits, only scheduling freedom — and the custom
    VJP passes the cotangent straight through (the barrier has no
    differentiation rule; the chunked forward's real backward runs the
    SINGLE-SHOT path anyway, see ``spmm_sharded``)."""
    return jax.lax.optimization_barrier(x)


def _stage_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _stage_bwd(_, g):
    return (g,)


_stage.defvjp(_stage_fwd, _stage_bwd)


def _run_chunked(run_one, b: jnp.ndarray, n_chunks: int) -> jnp.ndarray:
    """Double-buffered chunk pipeline over the columns of ``b``.

    Issues the staging of chunk k+1 BEFORE the matmul over chunk k and
    concatenates the per-chunk panels in fixed ascending order.  Column
    panels of a matmul are independent — each output column sees the
    same accumulation tree as in the single-shot call — so the result is
    bit-identical to ``run_one(b)``."""
    n = int(b.shape[-1])
    bounds = chunk_schedule(n, n_chunks)
    if len(bounds) == 1:
        return run_one(b)
    lo0, hi0 = bounds[0]
    nxt = _stage(b[:, lo0:hi0])
    parts = []
    for i, _ in enumerate(bounds):
        cur = nxt
        if i + 1 < len(bounds):
            lo, hi = bounds[i + 1]
            nxt = _stage(b[:, lo:hi])
        parts.append(run_one(cur))
    return jnp.concatenate(parts, axis=1)


# ----------------------------------------------------------------- planning
def plan_shards(a_p: bcsr_lib.BCSR, n_shards: int, *,
                rows_per_shard: Optional[int] = None,
                nnzb_per_shard: Optional[int] = None):
    """Balanced block-row partition of a (row-padded) BCSR.

    Returns ``(assign, shard_rows, loads, rps)``: the LPT bin assignment
    (``core.permute.shard_bins``), per-shard sorted block-row lists, the
    per-shard nonzero-block loads, and the (resolved) row-slot count."""
    nbr = a_p.n_block_rows
    rps = rows_per_shard or -(-max(nbr, 1) // n_shards)
    bpr = np.diff(a_p.rowptr)
    max_load = nnzb_per_shard
    if max_load is not None:
        # every virtual (unassigned) row slot costs one sentinel entry on
        # whichever shard it lands; reserve the worst case up front so the
        # LPT never fills headroom the sentinels need — an assignment that
        # passes here is GUARANTEED to fit the real+virtual budget check
        v_max = min(max(n_shards * rps - nbr, 0), rps)
        max_load = max_load - v_max
    assign = permute_lib.shard_bins(
        bpr, n_shards, rows_per_shard=rps, max_load=max_load)
    shard_rows = [np.flatnonzero(assign == s) for s in range(n_shards)]
    loads = np.asarray([int(bpr[r].sum()) for r in shard_rows], np.int64)
    return assign, shard_rows, loads, rps


def _local_stats(rows: np.ndarray, vals_real: np.ndarray, rps: int,
                 nnzb_ps: int, block) -> Tuple[int, int, int]:
    """(max_bpr, pad_pct, cv_pct) of one shard's padded local structure."""
    h, w = block
    bpr = np.bincount(rows, minlength=rps).astype(np.float64)
    mean = float(bpr.mean()) if bpr.size else 0.0
    cv = float(bpr.std() / mean) if mean > 0 else 0.0
    nnz = int(np.count_nonzero(vals_real))
    pad = 1.0 - nnz / max(nnzb_ps * h * w, 1)
    return (int(bpr.max()) if bpr.size else 0, int(round(pad * 100)),
            int(round(cv * 100)))


@obs_trace.spanned("prepare.shard")
def _prepare_sharded_host(a: bcsr_lib.BCSR, n_shards, *,
                          col_shards: int = 1,
                          reorder: str = "identity", tau: float = 0.7,
                          max_candidates: Optional[int] = None,
                          rows_per_shard: Optional[int] = None,
                          nnzb_per_shard: Optional[int] = None,
                          split_heavy_rows: bool = False):
    """Host-side (numpy) portion of ``prepare_sharded``: pre-reorder,
    partition, per-shard index structure, and the static ``ShardedMeta``
    with its per-shard structure stats.  Returns ``(host_arrays_dict,
    meta)``; ``prepare_sharded`` converts to device arrays,
    ``prepare_sharded_meta`` keeps only the meta.

    ``n_shards="auto"`` resolves the shard count through
    :func:`resolve_n_shards`.  ``split_heavy_rows=True`` switches to
    ENTRY-GRANULAR planning: block-rows heavier than the balanced budget
    split into contiguous entry fragments (``core.permute
    .split_heavy_rows``) that the LPT places like rows; non-primary
    fragments are recombined by a scatter-add at gather time (their row
    indices land in ``split_src`` / ``split_dst``)."""
    if isinstance(n_shards, str):
        if n_shards != "auto":
            raise ValueError(f"n_shards must be an int or 'auto', "
                             f"got {n_shards!r}")
        n_shards = resolve_n_shards(a).n_shards
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    h, w = a.block
    M, K = a.shape
    pre_perm = np.arange(M, dtype=np.int64)
    if reorder not in ("identity", "shard_balance"):
        with obs_trace.span("prepare.shard.reorder", scheme=reorder):
            a, pre_perm = permute_lib.permute_bcsr(
                a, reorder, tau=tau, max_candidates=max_candidates,
                n_shards=n_shards, granularity="block_row")
    a_p, real_g = a.ensure_nonempty_rows(return_mask=True)
    nbr, nbc = a_p.n_block_rows, a_p.n_block_cols
    rowptr = a_p.rowptr
    bpr = np.diff(rowptr)
    nnzb_g = a_p.nnzb

    if split_heavy_rows:
        if nnzb_per_shard is not None:
            raise ValueError(
                "split_heavy_rows derives its own per-shard budget from "
                "the balanced load; pinning nnzb_per_shard alongside it "
                "is contradictory — drop one of the two")
        # fragment planning: heavy rows split into contiguous entry runs
        # no larger than the balanced per-shard load, then the SAME LPT
        # places fragments into row slots (a fragment is a local row)
        cap = max(-(-nnzb_g // n_shards), 1)
        frag_row, frag_start, frag_len = permute_lib.split_heavy_rows(
            bpr, cap)
        n_frags = int(frag_row.size)
        rps = rows_per_shard or -(-max(n_frags, 1) // n_shards)
        if rps * n_shards < n_frags:
            raise ValueError(
                f"rows_per_shard={rps} too small for {n_frags} row "
                f"fragments over {n_shards} shards")
        assign = permute_lib.shard_bins(frag_len, n_shards,
                                        rows_per_shard=rps)
        shard_units = [np.flatnonzero(assign == s) for s in range(n_shards)]
        shard_loads = np.asarray([int(frag_len[u].sum())
                                  for u in shard_units], np.int64)
        unit_row, unit_start, unit_len = frag_row, frag_start, frag_len
    else:
        assign, shard_units, shard_loads, rps = plan_shards(
            a_p, n_shards, rows_per_shard=rows_per_shard,
            nnzb_per_shard=nnzb_per_shard)
        if rps * n_shards < nbr:
            raise ValueError(f"rows_per_shard={rps} too small for {nbr} "
                             f"block-rows over {n_shards} shards")
        unit_row = np.arange(nbr, dtype=np.int64)
        unit_start = np.zeros(nbr, np.int64)
        unit_len = bpr.astype(np.int64)

    # per-shard balance record: the LPT's real loads, before padding
    # equalizes the static shapes (obs gauges feed the dryrun/bench views)
    mean_load = float(shard_loads.mean()) if shard_loads.size else 0.0
    imbalance = (round(float(shard_loads.max()) / mean_load, 3)
                 if mean_load > 0 else 1.0)
    obs_trace.event("dist.shard_balance", n_shards=n_shards,
                    loads=shard_loads, imbalance=imbalance,
                    split_heavy_rows=bool(split_heavy_rows))
    obs_metrics.gauge("dist.shard_imbalance", n_shards=n_shards).set(
        imbalance)

    # per-shard entry lists (entries stay in a_p's global order; local ids
    # relabel planning units — block-rows, or fragments of them — to each
    # shard's slot space)
    needed = []
    per_shard = []
    for s in range(n_shards):
        units_s = shard_units[s]
        ent = np.concatenate(
            [rowptr[unit_row[u]] + unit_start[u] +
             np.arange(unit_len[u]) for u in units_s]
        ).astype(np.int64) if units_s.size else np.zeros(0, np.int64)
        lrow = np.repeat(np.arange(units_s.size),
                         unit_len[units_s]) if units_s.size \
            else np.zeros(0, np.int64)
        n_virtual = rps - units_s.size
        needed.append(ent.size + n_virtual)
        per_shard.append((units_s, ent, lrow, n_virtual))
    nnzb_ps = nnzb_per_shard or max(needed)
    if (nnzb_per_shard is None and not split_heavy_rows and n_shards > 1):
        # the derived budget is only honest when the heaviest block-row
        # fits a balanced shard: one dominant row would silently inflate
        # EVERY shard's padded budget to its size (the latent shard_bins
        # edge) — refuse, and point at the split path that handles it
        bal = -(-nnzb_g // n_shards) + rps
        if nnzb_ps > 2 * bal and int(bpr.max(initial=0)) > bal:
            raise ValueError(
                f"heaviest block-row ({int(bpr.max())} blocks) exceeds "
                f"the balanced per-shard budget ({bal}); the derived "
                f"budget {nnzb_ps} would over-allocate every shard — "
                "pass split_heavy_rows=True (entry-granular splits) or "
                "pin nnzb_per_shard explicitly")
    too_big = [s for s in range(n_shards) if needed[s] > nnzb_ps]
    if too_big:
        raise ValueError(
            f"shard(s) {too_big} need {[needed[s] for s in too_big]} entry "
            f"slots but the per-shard budget is {nnzb_ps}; raise "
            f"nnzb_per_shard or lower n_shards")
    nnzb_t_ps = nnzb_ps + nbc
    nnzb_g = a_p.nnzb
    sentinel = nnzb_g            # extra zero row appended to vals at apply

    src = np.full((n_shards, nnzb_ps), sentinel, np.int32)
    rows = np.zeros((n_shards, nnzb_ps), np.int32)
    cols = np.zeros((n_shards, nnzb_ps), np.int32)
    mask = np.zeros((n_shards, nnzb_ps), bool)
    t_perm = np.zeros((n_shards, nnzb_t_ps), np.int32)
    t_rows = np.zeros((n_shards, nnzb_t_ps), np.int32)
    t_cols = np.zeros((n_shards, nnzb_t_ps), np.int32)
    metas = []
    for s, (units_s, ent, lrow, n_virtual) in enumerate(per_shard):
        n_real = ent.size
        # one sentinel per virtual row keeps the nnz-stream kernel's
        # every-block-row-nonempty invariant; leftover budget pads row 0
        vrows = np.arange(units_s.size, rps)
        l_rows = np.concatenate([
            lrow, vrows, np.zeros(nnzb_ps - n_real - n_virtual, np.int64)])
        l_cols = np.concatenate([
            a_p.col_ids[ent].astype(np.int64),
            np.zeros(nnzb_ps - n_real, np.int64)])
        l_src = np.concatenate([
            ent, np.full(nnzb_ps - n_real, sentinel, np.int64)])
        l_mask = np.concatenate([
            real_g[ent], np.zeros(nnzb_ps - n_real, bool)])
        order = np.lexsort((l_cols, l_rows))
        rows[s] = l_rows[order]
        cols[s] = l_cols[order]
        src[s] = l_src[order]
        mask[s] = l_mask[order]
        # transpose structure: every local slot (sentinels hold zero blocks,
        # harmless) + one t-sentinel per t-block-row for full coverage —
        # the count is nnzb_ps + nbc by construction, shape-deterministic
        tt_rows = np.concatenate([cols[s].astype(np.int64),
                                  np.arange(nbc, dtype=np.int64)])
        tt_cols = np.concatenate([rows[s].astype(np.int64),
                                  np.zeros(nbc, np.int64)])
        tt_perm = np.concatenate([np.arange(nnzb_ps, dtype=np.int64),
                                  np.full(nbc, nnzb_ps, np.int64)])
        t_order = np.lexsort((tt_cols, tt_rows))
        t_rows[s] = tt_rows[t_order]
        t_cols[s] = tt_cols[t_order]
        t_perm[s] = tt_perm[t_order]
        max_bpr, pad_pct, cv_pct = _local_stats(
            rows[s], a_p.vals[ent], rps, nnzb_ps, (h, w))
        metas.append(ops.SparseMeta(
            shape=(rps * h, K), block=(h, w), n_block_rows=rps,
            n_block_cols=nbc, nnzb=nnzb_ps, nnzb_t=nnzb_t_ps,
            max_bpr=max_bpr, padding_ratio_pct=pad_pct, bpr_cv_pct=cv_pct,
            reorder="identity", n_shards=n_shards))

    # original row -> stacked output row: pre-reorder, then partition slot.
    # Each planning unit occupies one slot; a split block-row's PRIMARY
    # fragment (entry offset 0) carries the row through the gather, the
    # extras recombine via the split_src/split_dst scatter-add.
    inv_pre = permute_lib.invert_perm(pre_perm)
    slot_of_unit = np.empty(max(unit_row.size, 1), np.int64)
    for s in range(n_shards):
        us = shard_units[s]
        slot_of_unit[us] = s * rps + np.arange(us.size)
    primary = unit_start == 0
    slot_of_br = np.empty(nbr, np.int64)
    slot_of_br[unit_row[primary]] = slot_of_unit[: unit_row.size][primary]
    perm_rows = inv_pre                       # position after pre-reorder
    gather = slot_of_br[perm_rows // h] * h + perm_rows % h

    extra = np.flatnonzero(~primary)
    ar = np.arange(h, dtype=np.int64)
    x_rows = (unit_row[extra][:, None] * h + ar).ravel()    # a_p row space
    s_rows = (slot_of_unit[extra][:, None] * h + ar).ravel()
    valid = x_rows < M          # last block-row's pad rows carry no data
    split_src = s_rows[valid].astype(np.int64)
    split_dst = pre_perm[x_rows[valid]].astype(np.int64)

    host = {
        "vals": a_p.vals,
        "src_index": src,
        "row_ids": rows,
        "col_ids": cols,
        "real_mask": mask,
        "t_perm": t_perm,
        "t_row_ids": t_rows,
        "t_col_ids": t_cols,
        "gather_rows": gather,
        "split_src": split_src,
        "split_dst": split_dst,
    }
    meta = ShardedMeta(shape=(M, K), block=(h, w), n_shards=n_shards,
                       col_shards=col_shards, rows_per_shard=rps,
                       nnzb=nnzb_g, nnzb_per_shard=nnzb_ps,
                       nnzb_t_per_shard=nnzb_t_ps, shard_metas=tuple(metas),
                       reorder=reorder,
                       n_split_fragments=int(extra.size))
    return host, meta


def resolve_n_shards(a: bcsr_lib.BCSR, *, n: int = 512, max_shards: int = 8,
                     n_chunks: int = 2, tuner=None):
    """Resolve ``n_shards="auto"`` for a host BCSR: the autotuner's
    shard-count pick (``Autotuner.pick_shards`` — cache hit, else the
    analytic pipeline model over {1, 2, 4, 8} capped at ``max_shards``)
    evaluated on the operand's unsharded static meta.  Deterministic for
    a fixed (structure, n, max_shards, n_chunks) and cached under the v7
    ``shards|max=<M>|...|nk=<K>`` key.  Returns the ``ShardChoice``."""
    from repro.kernels import autotune
    meta = ops.prepare_sparse_meta(a)
    t = tuner or autotune.get_autotuner()
    return t.pick_shards(meta, n, max_shards=max_shards, n_chunks=n_chunks)


def prepare_sharded(a: bcsr_lib.BCSR, n_shards, *,
                    col_shards: int = 1, dtype=jnp.bfloat16,
                    reorder: str = "identity", tau: float = 0.7,
                    max_candidates: Optional[int] = None,
                    rows_per_shard: Optional[int] = None,
                    nnzb_per_shard: Optional[int] = None,
                    split_heavy_rows: bool = False
                    ) -> Tuple[ShardedArrays, ShardedMeta]:
    """Host BCSR -> row-partitioned device arrays + static sharded meta.

    ``n_shards`` is an int, or ``"auto"`` to resolve the shard count
    through :func:`resolve_n_shards` (analytic pick, cache-backed).
    ``reorder`` optionally applies a block-row permutation scheme FIRST
    (``jaccard`` | ``rcm`` — densify, then balance); the partition itself
    is the ``shard_balance`` assignment, so passing ``"shard_balance"`` or
    ``"identity"`` skips the pre-permutation.  ``rows_per_shard`` /
    ``nnzb_per_shard`` pin the per-shard static shapes (the model-weight
    path derives them from dims so scan-stacked layers agree); omitted,
    they are derived from the structure (tight fit).  Raises when the
    structure cannot fit the pinned budget — static shapes are a contract,
    not a best effort.  ``split_heavy_rows=True`` splits block-rows
    heavier than the balanced budget into entry fragments (extreme
    single-row skew; see module docstring).

    Example (4-way partition of a 320x256 operand, local execution):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.launch import dist_spmm
    >>> a = bcsr_lib.random_bcsr_exact(7, (320, 256), (16, 16), nnzb=80)
    >>> sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    >>> (smeta.n_shards, smeta.rows_per_shard, len(smeta.shard_metas))
    (4, 5, 4)
    >>> all(m.max_bpr > 0 for m in smeta.shard_metas)  # real structure stats
    True
    """
    host, meta = _prepare_sharded_host(
        a, n_shards, col_shards=col_shards, reorder=reorder, tau=tau,
        max_candidates=max_candidates, rows_per_shard=rows_per_shard,
        nnzb_per_shard=nnzb_per_shard, split_heavy_rows=split_heavy_rows)
    arrays = ShardedArrays(
        vals=jnp.asarray(host["vals"], dtype=dtype),
        src_index=jnp.asarray(host["src_index"], jnp.int32),
        row_ids=jnp.asarray(host["row_ids"], jnp.int32),
        col_ids=jnp.asarray(host["col_ids"], jnp.int32),
        real_mask=jnp.asarray(host["real_mask"]),
        t_perm=jnp.asarray(host["t_perm"], jnp.int32),
        t_row_ids=jnp.asarray(host["t_row_ids"], jnp.int32),
        t_col_ids=jnp.asarray(host["t_col_ids"], jnp.int32),
        gather_rows=jnp.asarray(host["gather_rows"], jnp.int32),
        split_src=jnp.asarray(host["split_src"], jnp.int32),
        split_dst=jnp.asarray(host["split_dst"], jnp.int32),
    )
    return arrays, meta


def prepare_sharded_meta(a: bcsr_lib.BCSR, n_shards, *,
                         col_shards: int = 1, reorder: str = "identity",
                         tau: float = 0.7,
                         max_candidates: Optional[int] = None,
                         rows_per_shard: Optional[int] = None,
                         nnzb_per_shard: Optional[int] = None,
                         split_heavy_rows: bool = False) -> ShardedMeta:
    """The static ``ShardedMeta`` that ``prepare_sharded`` would return,
    WITHOUT building device arrays — bit-identical by construction (same
    host pipeline, dtype only affects the arrays).

    The model path uses this (memoized, via
    ``core.sparse_linear.sparse_linear_meta``) to re-derive the true
    per-shard structure stats of a deterministic weight pattern at trace
    time, so ``apply_sparse_linear`` dispatches each shard on its real
    fingerprint — heterogeneous per-shard picks, not one collapsed
    streaming choice."""
    return _prepare_sharded_host(
        a, n_shards, col_shards=col_shards, reorder=reorder, tau=tau,
        max_candidates=max_candidates, rows_per_shard=rows_per_shard,
        nnzb_per_shard=nnzb_per_shard, split_heavy_rows=split_heavy_rows)[1]


def prepare(a: bcsr_lib.BCSR, n_shards, *, meta_only: bool = False,
            col_shards: int = 1, dtype=jnp.bfloat16,
            reorder: str = "identity", tau: float = 0.7,
            max_candidates: Optional[int] = None,
            rows_per_shard: Optional[int] = None,
            nnzb_per_shard: Optional[int] = None,
            split_heavy_rows: bool = False):
    """Unified entry point for the sharded prepare twins (PR 8).

    ``meta_only=False`` (default) delegates to :func:`prepare_sharded`
    and returns ``(ShardedArrays, ShardedMeta)``; ``meta_only=True``
    delegates to :func:`prepare_sharded_meta` and returns the
    ``ShardedMeta`` alone (``dtype`` is ignored — meta is dtype-free by
    construction).  The twins stay as documented aliases; this mirrors
    ``kernels.ops.prepare`` for the distributed op family.

    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.launch import dist_spmm
    >>> a = bcsr_lib.random_bcsr_exact(7, (320, 256), (16, 16), nnzb=80)
    >>> _, smeta = dist_spmm.prepare(a, 4)
    >>> dist_spmm.prepare(a, 4, meta_only=True) == smeta
    True
    """
    kw = dict(col_shards=col_shards, reorder=reorder, tau=tau,
              max_candidates=max_candidates, rows_per_shard=rows_per_shard,
              nnzb_per_shard=nnzb_per_shard, split_heavy_rows=split_heavy_rows)
    if meta_only:
        return prepare_sharded_meta(a, n_shards, **kw)
    return prepare_sharded(a, n_shards, dtype=dtype, **kw)


# ---------------------------------------------------------------- execution
def _combine_splits(out: jnp.ndarray, out_pad: jnp.ndarray,
                    arrays: ShardedArrays) -> jnp.ndarray:
    """Add non-primary row-fragment partial sums back into their original
    rows (entry-granular splits).  No-op (same array) when the operand
    was prepared without splits — the default path stays byte-identical
    to the pre-split implementation."""
    src = arrays.split_src
    if src is None or int(src.shape[0]) == 0:
        return out
    return out.at[arrays.split_dst].add(jnp.take(out_pad, src, axis=0))


def _resolve_shard_choices(smeta: ShardedMeta, n_local: int, backend: str,
                           bn: int) -> Tuple[Tuple[str, int], ...]:
    """Per-shard (backend, bn): ``auto`` consults the v7 per-shard
    fingerprints, so a skewed shard can take another variant than its
    uniform neighbors — the per-structure choice the global dispatch
    could not make.  ``n_local`` is the panel width each shard
    ACTUALLY multiplies (full N in local mode; N / col_shards under the 2D
    shard_map) so cached picks come from the right N bucket."""
    return tuple(ops.resolve_backend(backend, bn, m, n_local)
                 for m in smeta.shard_metas)


def _branch_meta(smeta: ShardedMeta, members) -> ops.SparseMeta:
    """Representative meta for one switch branch: shapes are shared by
    construction; max_bpr takes the branch max, the bound of every member
    shard."""
    first = smeta.shard_metas[members[0]]
    return dataclasses.replace(
        first, max_bpr=max(smeta.shard_metas[i].max_bpr for i in members))


@jaxmon.monitor(name="launch.spmm_sharded")
def spmm_sharded(arrays: ShardedArrays, smeta: ShardedMeta, b: jnp.ndarray,
                 *, backend: str = "auto", bn: int = 512,
                 interpret: bool = False, mesh=None,
                 out_dtype=None, n_chunks: int = 1) -> jnp.ndarray:
    """C = A @ B over the row-partitioned operand, original row order.

    ``mesh=None`` runs the identical per-shard schedule in-process (the
    single-device fallback); a mesh with an ``AXIS_ROW`` axis of size
    ``n_shards`` (and ``AXIS_COL`` of size ``col_shards`` when 2D) runs it
    as a ``shard_map``.  Differentiable w.r.t. ``arrays.vals`` and ``b``
    through the per-shard custom VJPs; partial dB contributions psum
    across row shards via the shard_map transpose.

    ``backend="auto"`` resolves one (variant, bn) PER SHARD from the v7
    per-shard fingerprints; heterogeneous picks dispatch via ``lax.switch``
    on the mesh axis index.

    ``n_chunks > 1`` pipelines the panel in ascending column chunks —
    chunk k+1's operand staging is issued before chunk k's matmul
    (``_run_chunked``).  Kernel picks are resolved at the FULL panel
    width either way, so the chunked result is bit-identical to
    ``n_chunks=1`` (per-column accumulation trees are unchanged).  The
    backward pass runs the SINGLE-SHOT schedule regardless of
    ``n_chunks`` (a ``custom_vjp`` over the chunked forward): chunking
    the dvals contraction would split its column sum into a different
    accumulation tree, and since the chunked primal is value-identical
    to the unchunked one, the unchunked VJP is exactly its VJP — grads
    stay bitwise-stable across every chunk depth.

    Example (in-process fallback, checked against the unsharded oracle):

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import ops
    >>> from repro.launch import dist_spmm
    >>> a = bcsr_lib.random_bcsr_exact(7, (320, 256), (16, 16), nnzb=80)
    >>> sharr, smeta = dist_spmm.prepare_sharded(a, 4, dtype=jnp.float32)
    >>> b = jnp.asarray(np.random.default_rng(0).standard_normal(
    ...     (256, 32)).astype(np.float32))
    >>> c = dist_spmm.spmm_sharded(sharr, smeta, b, backend="xla")
    >>> arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
    >>> bool(jnp.allclose(c, ops.spmm(arrays, meta, b, backend="xla"),
    ...                   atol=1e-4))
    True
    """
    if obs_trace.enabled():
        n = int(b.shape[-1])
        sched = chunk_schedule(n, n_chunks)
        obs_trace.event("dist.chunk_schedule", n=n, n_chunks=len(sched),
                        n_shards=smeta.n_shards, backend=backend,
                        schedule=sched)
    obs_metrics.gauge("dist.n_chunks").set(n_chunks)
    if n_chunks > 1:
        kw = dict(backend=backend, bn=bn, interpret=interpret, mesh=mesh,
                  out_dtype=out_dtype)

        @jax.custom_vjp
        def call(arrs, bb):
            return _spmm_sharded_exec(arrs, smeta, bb, n_chunks=n_chunks,
                                      **kw)

        def fwd(arrs, bb):
            return (_spmm_sharded_exec(arrs, smeta, bb, n_chunks=n_chunks,
                                       **kw), (arrs, bb))

        def bwd(res, g):
            arrs, bb = res
            _, vjp = jax.vjp(
                lambda a_, b_: _spmm_sharded_exec(a_, smeta, b_, n_chunks=1,
                                                  **kw), arrs, bb)
            return vjp(g)

        call.defvjp(fwd, bwd)
        return call(arrays, b)
    return _spmm_sharded_exec(arrays, smeta, b, backend=backend, bn=bn,
                              interpret=interpret, mesh=mesh,
                              out_dtype=out_dtype, n_chunks=n_chunks)


def _spmm_sharded_exec(arrays: ShardedArrays, smeta: ShardedMeta,
                       b: jnp.ndarray, *, backend: str, bn: int,
                       interpret: bool, mesh, out_dtype,
                       n_chunks: int) -> jnp.ndarray:
    M, K = smeta.shape
    N = int(b.shape[-1])
    S = smeta.n_shards

    zero = jnp.zeros((1,) + tuple(arrays.vals.shape[1:]), arrays.vals.dtype)
    vals_ext = jnp.concatenate([arrays.vals, zero], axis=0)

    if mesh is None:
        # local mode multiplies the FULL panel per shard — resolve picks
        # for N, not N / col_shards (and never for a chunk's width: the
        # pick must not depend on n_chunks or bitwise identity breaks)
        choices = _resolve_shard_choices(smeta, N, backend, bn)
        arrs = [ops.SparseArrays(
            jnp.take(vals_ext, arrays.src_index[s], axis=0),
            arrays.row_ids[s], arrays.col_ids[s],
            arrays.real_mask[s], arrays.t_perm[s], arrays.t_row_ids[s],
            arrays.t_col_ids[s]) for s in range(S)]

        def run_all(bc):
            outs = []
            for s in range(S):
                be, bn_s = choices[s]
                outs.append(ops.spmm(arrs[s], smeta.shard_metas[s], bc,
                                     backend=be, bn=bn_s,
                                     interpret=interpret,
                                     out_dtype=out_dtype))
            return jnp.concatenate(outs, axis=0)

        out_pad = _run_chunked(run_all, b, n_chunks)
        out = jnp.take(out_pad, arrays.gather_rows, axis=0)
        return _combine_splits(out, out_pad, arrays)

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis_sizes.get(AXIS_ROW) != S:
        raise ValueError(
            f"mesh axis {AXIS_ROW!r} must have size {S} "
            f"(got {axis_sizes.get(AXIS_ROW)}); build one with "
            "dist_spmm.make_spmm_mesh")
    C = smeta.col_shards
    if C > 1 and axis_sizes.get(AXIS_COL) != C:
        raise ValueError(
            f"mesh axis {AXIS_COL!r} must have size {C} "
            f"(got {axis_sizes.get(AXIS_COL)})")
    choices = _resolve_shard_choices(smeta, -(-N // C), backend, bn)

    n_pad = (-N) % C
    b_p = jnp.pad(b, ((0, 0), (0, n_pad))) if n_pad else b

    keys = list(dict.fromkeys(choices))
    branch_of = [keys.index(c) for c in choices]
    branch_metas = [
        _branch_meta(smeta, [i for i in range(S) if branch_of[i] == k])
        for k in range(len(keys))]

    def _branch(k):
        be, bn_k = keys[k]
        meta_k = branch_metas[k]

        def run(sv, ri, ci, rm, tp, tr, tc, bloc):
            arr = ops.SparseArrays(sv, ri, ci, rm, tp, tr, tc)

            def one(bc):
                return ops.spmm(arr, meta_k, bc, backend=be, bn=bn_k,
                                interpret=interpret, out_dtype=out_dtype)
            return _run_chunked(one, bloc, n_chunks)
        return run

    def body(ve, si, ri, ci, rm, tp, tr, tc, bloc):
        # the per-shard weight gather happens HERE, on the local slice of
        # src_index against the replicated flat vals — no device ever
        # materializes the full [S, nnzb_ps, h, w] stack
        sv = jnp.take(ve, si[0], axis=0)
        operands = (sv, ri[0], ci[0], rm[0], tp[0], tr[0], tc[0], bloc)
        if len(keys) == 1:
            return _branch(0)(*operands)
        idx = jax.lax.axis_index(AXIS_ROW)
        sel = jnp.asarray(branch_of, jnp.int32)[idx]
        return jax.lax.switch(sel, [_branch(k) for k in range(len(keys))],
                              *operands)

    shard_spec = P(AXIS_ROW)
    b_spec = P(None, AXIS_COL) if C > 1 else P()
    out_spec = P(AXIS_ROW, AXIS_COL) if C > 1 else P(AXIS_ROW)
    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(),) + (shard_spec,) * 7 + (b_spec,),
                      out_specs=out_spec, check_vma=False)
    out_pad = f(vals_ext, arrays.src_index, arrays.row_ids, arrays.col_ids,
                arrays.real_mask, arrays.t_perm, arrays.t_row_ids,
                arrays.t_col_ids, b_p)
    # padding rows are dropped by the gather; its transpose scatters exact
    # zeros back into them, so grads match the unsharded path bit-for-bit
    # on the real support
    out = jnp.take(out_pad, arrays.gather_rows, axis=0)
    out = _combine_splits(out, out_pad, arrays)
    return out[:, :N]


# ------------------------------------------------------------------- tuning
def tune_shards(arrays: ShardedArrays, smeta: ShardedMeta, n: int, *,
                interpret: bool = False, warmup: int = 1, iters: int = 3,
                rng_seed: int = 0, tuner=None) -> dict:
    """Timed per-shard micro-sweep (the sharded analogue of
    ``Autotuner.tune``): times every registered candidate on each shard's
    LOCAL slice and caches the winner under the shard's v7 fingerprint,
    so later ``backend="auto"`` dispatch picks measured winners per shard.
    Shards whose fingerprints coincide (well-balanced partitions — the
    common case) are timed once.  Failed candidates are recorded like
    ``Autotuner.tune`` records them, and a shard whose default candidate
    fails raises.  Returns {fingerprint_key: choice}."""
    from repro.kernels import autotune
    tuner = tuner or autotune.get_autotuner()
    rng = np.random.default_rng(rng_seed)
    b = jnp.asarray(rng.standard_normal((smeta.shape[1], n)),
                    dtype=jnp.float32)
    zero = jnp.zeros((1,) + tuple(arrays.vals.shape[1:]), arrays.vals.dtype)
    vals_ext = jnp.concatenate([arrays.vals, zero], axis=0)

    tuned: dict = {}
    for s, meta_s in enumerate(smeta.shard_metas):
        fp = autotune.fingerprint(meta_s, n)
        if fp.key() in tuned:
            continue
        arr = ops.SparseArrays(
            jnp.take(vals_ext, arrays.src_index[s], axis=0),
            arrays.row_ids[s], arrays.col_ids[s], arrays.real_mask[s],
            arrays.t_perm[s], arrays.t_row_ids[s], arrays.t_col_ids[s])
        cand = {}
        for name in autotune.variant_names():
            v = autotune.get_variant(name)
            if not v.supported(meta_s):
                continue
            bns = {autotune.pick_bn(meta_s, n, v.bn_candidates)}
            bns.update(bn for bn in v.bn_candidates if bn <= max(n, 128))
            for bn in sorted(bns):
                cand[f"{name}/bn{bn}"] = (name, bn)
        cand.setdefault(
            f"{autotune.DEFAULT_VARIANT}/bn{autotune.DEFAULT_BN}",
            (autotune.DEFAULT_VARIANT, autotune.DEFAULT_BN))
        timings: autotune.Timings = {}
        for label, (name, bn) in cand.items():
            backend = autotune.get_variant(name).backend
            fn = jax.jit(lambda bb, _be=backend, _bn=bn: ops.spmm(
                arr, meta_s, bb, backend=_be, bn=_bn, interpret=interpret))
            autotune.time_candidate(timings, label, fn, b, warmup=warmup,
                                    iters=iters, key=fp.key(), shard=s)
        best = autotune.measured_winner(
            timings, f"{autotune.DEFAULT_VARIANT}/bn{autotune.DEFAULT_BN}")
        name, bn = cand[best]
        choice = autotune.KernelChoice(name, bn, source="measured",
                                       predicted_us=timings[best] * 1e6)
        tuner.put(fp, choice, persist=True)
        tuned[fp.key()] = choice
    return tuned


def tune_shard_count(a: bcsr_lib.BCSR, n: int, *, max_shards: int = 8,
                     n_chunks: int = 1, backend: str = "auto", bn: int = 512,
                     interpret: bool = False, warmup: int = 1, iters: int = 3,
                     rng_seed: int = 0, tuner=None):
    """Timed shard-count micro-sweep: the measured counterpart of
    :func:`resolve_n_shards` (the optional half of the autotune axis —
    the analytic pick never blocks on it).  Prepares the operand at each
    candidate S, times the end-to-end local ``spmm_sharded`` with the
    requested chunk depth, and caches the winner in the autotuner's
    shard-entry section under the operand's v7 ``nk=`` fingerprint so
    later ``resolve_n_shards`` calls return the measured choice.  Smaller
    S wins ties (within 2% — partition overhead noise).  A candidate S
    that fails to run is recorded (``autotune.time_candidate``); if none
    runs, this raises.  Returns the ``ShardChoice``."""
    from repro.kernels import autotune
    tuner = tuner or autotune.get_autotuner()
    meta = ops.prepare_sparse_meta(a)
    fp = autotune.fingerprint(meta, n, n_chunks=n_chunks)
    rng = np.random.default_rng(rng_seed)
    b = jnp.asarray(rng.standard_normal((a.shape[1], n)), jnp.float32)

    timings: autotune.Timings = {}
    for s in autotune.shard_candidates(max_shards, meta.n_block_rows):
        try:
            sharr, smeta = prepare_sharded(a, s, dtype=jnp.float32)
        except ValueError:      # unfittable at this S — not a candidate
            continue
        fn = jax.jit(lambda bb, _a=sharr, _m=smeta: spmm_sharded(
            _a, _m, bb, backend=backend, bn=bn, interpret=interpret,
            n_chunks=n_chunks))
        autotune.time_candidate(timings, f"S{s}", fn, b, warmup=warmup,
                                iters=iters, key=fp.key())
    measured = {int(k[1:]): t for k, t in timings.items()
                if not isinstance(t, str)}
    if not measured:
        raise RuntimeError(f"tune_shard_count: no shard count ran; "
                           f"timings: {timings}")
    t_best = min(measured.values())
    best = next(s for s in sorted(measured) if measured[s] <= t_best * 1.02)
    choice = autotune.ShardChoice(best, source="measured",
                                  predicted_us=measured[best] * 1e6)
    tuner.put_shards(fp, max_shards, choice, persist=True)
    return choice


# ---------------------------------------------------------------- reporting
def shard_balance_stats(a: bcsr_lib.BCSR, n_shards: int, *,
                        rows_per_shard: Optional[int] = None) -> dict:
    """Host-side per-shard nnzb balance report (dry-run / benchmarks).

    ``imbalance`` is max/mean per-shard load (1.0 = perfect);
    ``contig_imbalance`` is the same for a naive contiguous equal-row
    split — the balance the LPT assignment buys vs doing nothing."""
    a_p = a.ensure_nonempty_rows()
    _, _, loads, rps = plan_shards(a_p, n_shards,
                                   rows_per_shard=rows_per_shard)
    bpr = np.diff(a_p.rowptr)
    nbr = bpr.size
    contig = np.asarray(
        [int(bpr[s * rps: (s + 1) * rps].sum()) for s in range(n_shards)],
        np.int64)
    mean = float(loads.mean()) if n_shards else 0.0

    def imb(x):
        m = float(x.mean())
        return round(float(x.max()) / m, 4) if m > 0 else 1.0

    return {
        "n_shards": int(n_shards),
        "n_block_rows": int(nbr),
        "rows_per_shard": int(rps),
        "nnzb": int(a_p.nnzb),
        "loads": [int(x) for x in loads],
        "load_mean": round(mean, 2),
        "load_max": int(loads.max()) if n_shards else 0,
        "imbalance": imb(loads),
        "contig_imbalance": imb(contig),
        "load_cv_pct": int(round(100 * float(loads.std()) / mean))
        if mean > 0 else 0,
    }
