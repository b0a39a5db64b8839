"""Pre-launch static verification of schedules, grids, and VMEM budgets.

Everything that makes the TC-shaped kernels correct is decided BEFORE any
device array exists: the (block-row x slot) static schedule, its sentinel
padding convention, the grid derived from (meta, n, bn), and the VMEM
working set.  This pass re-derives each of those symbolically — pure
numpy on the host index structure — and checks the contracts the kernels
assume:

* **coverage** — every live nnzb slot appears in the schedule exactly
  once; sentinels (entry 0 for the spmm family, entry ``nnzb`` for
  sddmm/attn) appear ONLY on padding slots;
* **bounds** — every index the schedule can hand an index_map stays
  inside the derived grid / operand shapes;
* **shape** — block shapes divide the matrix dims or rag them by less
  than one block (``nbr == ceil(M/h)``, ``nbc == ceil(K/w)``);
* **VMEM** — the per-cell working set (``repro.analysis.workspace``, the
  same estimator the autotuner and the attention benchmark use) fits a
  configurable budget when double-buffered.

Entry points: ``verify_meta`` / ``verify_sharded_meta`` (invariants of a
meta alone), ``verify_schedule`` (a concrete schedule against its meta),
``assert_launch_ok`` (the opt-in ``REPRO_VERIFY_LAUNCH=1`` hook inside
``ops.resolve_backend``), ``verify_summary`` (the dict ``launch.dryrun``
embeds), and ``run_verify`` (the CLI pass over the structure zoo).

>>> import numpy as np
>>> from repro.core import bcsr as bcsr_lib
>>> from repro.kernels import ops
>>> a = bcsr_lib.random_bcsr_exact(0, (128, 128), (16, 16), 24)
>>> meta = ops.prepare_sparse_meta(a)
>>> verify_meta(meta)
[]
>>> fi, fc = sddmm_row_loop_schedule_host(a.row_ids, a.col_ids,
...                                       meta.n_block_rows, meta.max_bpr)
>>> verify_schedule("sddmm", fi, fc, a.row_ids, a.col_ids, meta)
[]
>>> bad = fi.copy(); bad[np.flatnonzero(fi != meta.nnzb)[0]] = meta.nnzb
>>> len(verify_schedule("sddmm", bad, fc, a.row_ids, a.col_ids, meta)) > 0
True
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.analysis import workspace
from repro.analysis.report import Finding

FAMILIES = ("spmm", "sddmm", "attn")


class LaunchError(ValueError):
    """A meta/schedule/budget contract is violated for the requested
    launch — raised by ``assert_launch_ok`` before any kernel dispatch."""


# ------------------------------------------------------- schedule mirrors
def spmm_row_loop_schedule_host(row_ids, col_ids, n_block_rows: int,
                                max_bpr: int):
    """Host-numpy twin of ``ops._row_loop_schedule`` (and of the host
    builder ``ops.make_row_loop_schedule``): per (block-row, slot) the
    entry index and block-col, padding slots pointing at entry 0 / col 0,
    plus the per-row live count ``row_len`` the kernel masks its loop
    with."""
    row_ids = np.asarray(row_ids, np.int64)
    col_ids = np.asarray(col_ids, np.int64)
    nnzb = row_ids.shape[0]
    row_len = np.bincount(row_ids, minlength=n_block_rows)
    rowptr = np.concatenate([[0], np.cumsum(row_len)])
    slot = np.arange(nnzb) - rowptr[row_ids]
    pos = row_ids * max_bpr + slot
    flat_idx = np.zeros(n_block_rows * max_bpr, np.int32)
    flat_col = np.zeros(n_block_rows * max_bpr, np.int32)
    flat_idx[pos] = np.arange(nnzb, dtype=np.int32)
    flat_col[pos] = col_ids
    return flat_idx, flat_col, row_len.astype(np.int32)


def sddmm_row_loop_schedule_host(row_ids, col_ids, n_block_rows: int,
                                 max_bpr: int):
    """Host-numpy twin of ``ops._sddmm_row_loop_schedule`` AND of the
    fused-attention schedule (``models.attention._fused_inputs`` builds
    the identical arrays): padding slots point at the sentinel entry
    ``nnzb`` instead of entry 0."""
    row_ids = np.asarray(row_ids, np.int64)
    col_ids = np.asarray(col_ids, np.int64)
    nnzb = row_ids.shape[0]
    row_len = np.bincount(row_ids, minlength=n_block_rows)
    rowptr = np.concatenate([[0], np.cumsum(row_len)])
    slot = np.arange(nnzb) - rowptr[row_ids]
    pos = row_ids * max_bpr + slot
    flat_idx = np.full(n_block_rows * max_bpr, nnzb, np.int32)
    flat_col = np.zeros(n_block_rows * max_bpr, np.int32)
    flat_idx[pos] = np.arange(nnzb, dtype=np.int32)
    flat_col[pos] = col_ids
    return flat_idx, flat_col


def build_schedule(family: str, row_ids, col_ids, meta):
    """(flat_idx, flat_col, row_len|None) for ``family`` from the sorted
    entry list — the schedule the kernels would actually launch with."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; want one of {FAMILIES}")
    if family == "spmm":
        return spmm_row_loop_schedule_host(
            row_ids, col_ids, meta.n_block_rows, meta.max_bpr)
    fi, fc = sddmm_row_loop_schedule_host(
        row_ids, col_ids, meta.n_block_rows, meta.max_bpr)
    return fi, fc, None


# ------------------------------------------------------- meta invariants
def verify_meta(meta) -> list:
    """Structural invariants of one ``SparseMeta`` — no arrays involved.

    Dims-only specs metas (``max_bpr == 0``) are legal: they carry shape
    budgets, not a realized structure, and the row_loop family refuses
    them separately.  Shard-local metas (``n_shards > 1``) may contain
    duplicate (row, col) slots (padding), so the distinct-entries bound
    ``nnzb <= nbr * nbc`` applies only to whole-matrix metas."""
    errs = []
    h, w = meta.block
    M, K = meta.shape
    nbr, nbc = meta.n_block_rows, meta.n_block_cols
    if h <= 0 or w <= 0:
        errs.append(f"block {meta.block} must be positive")
        return errs
    if M <= 0 or K <= 0:
        errs.append(f"shape {meta.shape} must be positive")
        return errs
    if nbr != -(-M // h):
        errs.append(f"n_block_rows={nbr} != ceil({M}/{h})={-(-M // h)} "
                    "(block must divide or rag M by < one block)")
    if nbc != -(-K // w):
        errs.append(f"n_block_cols={nbc} != ceil({K}/{w})={-(-K // w)}")
    if meta.nnzb < 0:
        errs.append(f"nnzb={meta.nnzb} < 0")
    if meta.n_shards == 1 and meta.nnzb > nbr * nbc:
        errs.append(f"nnzb={meta.nnzb} exceeds the {nbr}x{nbc} distinct "
                    "block capacity of a whole-matrix meta")
    if not (meta.nnzb <= meta.nnzb_t <= meta.nnzb + nbc):
        errs.append(f"nnzb_t={meta.nnzb_t} outside [nnzb, nnzb + nbc] = "
                    f"[{meta.nnzb}, {meta.nnzb + nbc}] (transpose structure "
                    "adds at most one sentinel per t-block-row)")
    if meta.max_bpr < 0:
        errs.append(f"max_bpr={meta.max_bpr} < 0")
    elif meta.n_shards == 1 and meta.max_bpr > nbc:
        # shard-local metas (n_shards > 1) may exceed nbc: padding slots
        # duplicate (row 0, col 0) and count toward the schedule bound
        errs.append(f"max_bpr={meta.max_bpr} outside [0, n_block_cols={nbc}]")
    if meta.max_bpr > 0:
        if meta.nnzb > nbr * meta.max_bpr:
            errs.append(
                f"schedule capacity violated: nnzb={meta.nnzb} > "
                f"n_block_rows*max_bpr={nbr * meta.max_bpr} — some entry "
                "has no (row, slot) to live in")
        if meta.max_bpr > meta.nnzb:
            errs.append(f"max_bpr={meta.max_bpr} > nnzb={meta.nnzb}")
        if meta.n_shards == 1 and meta.nnzb < nbr:
            errs.append(
                f"nnzb={meta.nnzb} < n_block_rows={nbr} with max_bpr > 0 — "
                "prepared metas pad every block-row nonempty")
    if not (0 <= meta.padding_ratio_pct <= 100):
        errs.append(f"padding_ratio_pct={meta.padding_ratio_pct} not a pct")
    if meta.bpr_cv_pct < 0:
        errs.append(f"bpr_cv_pct={meta.bpr_cv_pct} < 0")
    if meta.n_shards < 1:
        errs.append(f"n_shards={meta.n_shards} < 1")
    return errs


def verify_sharded_meta(smeta) -> list:
    """Invariants of a ``ShardedMeta``: global bookkeeping plus every
    per-shard ``SparseMeta`` (checked via ``verify_meta``)."""
    errs = []
    h, w = smeta.block
    M, K = smeta.shape
    nbr = -(-M // h)
    if smeta.n_shards < 1 or smeta.col_shards < 1:
        errs.append(f"n_shards={smeta.n_shards}, col_shards="
                    f"{smeta.col_shards} must be >= 1")
        return errs
    if len(smeta.shard_metas) != smeta.n_shards:
        errs.append(f"{len(smeta.shard_metas)} shard_metas != n_shards="
                    f"{smeta.n_shards}")
        return errs
    if smeta.rows_per_shard * smeta.n_shards < nbr:
        errs.append(f"rows_per_shard={smeta.rows_per_shard} x n_shards="
                    f"{smeta.n_shards} cannot hold {nbr} block-rows")
    if smeta.nnzb_t_per_shard != smeta.nnzb_per_shard + -(-K // w):
        errs.append(f"nnzb_t_per_shard={smeta.nnzb_t_per_shard} != "
                    f"nnzb_per_shard + n_block_cols (shape-deterministic "
                    "t-structure contract)")
    for s, m in enumerate(smeta.shard_metas):
        sub = verify_meta(m)
        errs += [f"shard {s}: {e}" for e in sub]
        if m.shape != (smeta.rows_per_shard * h, K):
            errs.append(f"shard {s}: shape {m.shape} != "
                        f"{(smeta.rows_per_shard * h, K)}")
        if m.nnzb != smeta.nnzb_per_shard:
            errs.append(f"shard {s}: nnzb={m.nnzb} != nnzb_per_shard="
                        f"{smeta.nnzb_per_shard}")
        if m.block != smeta.block:
            errs.append(f"shard {s}: block {m.block} != {smeta.block}")
        if m.n_shards != smeta.n_shards:
            errs.append(f"shard {s}: n_shards={m.n_shards} != "
                        f"{smeta.n_shards}")
    return errs


# ---------------------------------------------------- schedule verification
def verify_schedule(family: str, flat_idx, flat_col, row_ids, col_ids,
                    meta, row_len=None) -> list:
    """Check one realized (block-row x slot) schedule against its meta.

    ``family`` fixes the sentinel convention: ``"spmm"`` pads with entry 0
    and needs ``row_len`` (the kernel's loop mask); ``"sddmm"``/``"attn"``
    pad with the sentinel entry index ``nnzb``.  Returns a list of error
    strings — empty means the schedule covers every live slot exactly
    once, sentinels sit only on padding, every index is in bounds, and
    the (row, col) bookkeeping is self-consistent."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; want one of {FAMILIES}")
    errs = []
    flat_idx = np.asarray(flat_idx, np.int64)
    flat_col = np.asarray(flat_col, np.int64)
    row_ids = np.asarray(row_ids, np.int64)
    col_ids = np.asarray(col_ids, np.int64)
    nnzb, nbr, nbc = meta.nnzb, meta.n_block_rows, meta.n_block_cols
    max_bpr = meta.max_bpr
    if max_bpr <= 0:
        return [f"{family}: meta.max_bpr={max_bpr} — no static schedule "
                "exists for a dims-only meta"]
    want_len = nbr * max_bpr
    if flat_idx.shape[0] != want_len or flat_col.shape[0] != want_len:
        return [f"{family}: schedule length {flat_idx.shape[0]} != "
                f"n_block_rows*max_bpr={want_len}"]
    if row_ids.shape[0] != nnzb:
        return [f"{family}: entry list length {row_ids.shape[0]} != "
                f"meta.nnzb={nnzb}"]
    if row_ids.size and (row_ids.min() < 0 or row_ids.max() >= nbr):
        errs.append(f"{family}: entry row_ids outside [0, {nbr})")
    if col_ids.size and (col_ids.min() < 0 or col_ids.max() >= nbc):
        errs.append(f"{family}: entry col_ids outside [0, {nbc})")
    if np.any(np.diff(row_ids) < 0):
        errs.append(f"{family}: entry list not sorted row-major "
                    "(row_ids must be nondecreasing)")
    if errs:
        return errs

    counts = np.bincount(row_ids, minlength=nbr)
    if counts.max(initial=0) > max_bpr:
        return [f"{family}: a block-row holds {int(counts.max())} entries "
                f"> max_bpr={max_bpr} — schedule cannot represent it"]
    slots = np.arange(want_len) % max_bpr
    seg_row = np.arange(want_len) // max_bpr
    if family == "spmm":
        if row_len is None:
            return [f"{family}: row_len is required (the kernel's loop "
                    "bound) for the spmm family"]
        row_len = np.asarray(row_len, np.int64)
        if row_len.shape[0] != nbr:
            return [f"{family}: row_len length {row_len.shape[0]} != "
                    f"n_block_rows={nbr}"]
        if not np.array_equal(row_len, counts):
            bad = int(np.flatnonzero(row_len != counts)[0])
            errs.append(
                f"{family}: row_len[{bad}]={int(row_len[bad])} != true "
                f"entry count {int(counts[bad])} — the loop mask drops or "
                "double-visits slots")
        live = slots < row_len[seg_row]
        # in-bounds: every slot (live or padding) indexes a real entry
        if flat_idx.min() < 0 or flat_idx.max() >= max(nnzb, 1):
            errs.append(f"{family}: flat_idx outside [0, nnzb={nnzb}) — "
                        "spmm padding must reuse entry 0, not a sentinel")
        pad_bad = np.flatnonzero(~live & ((flat_idx != 0) | (flat_col != 0)))
        if pad_bad.size:
            errs.append(f"{family}: {pad_bad.size} padding slot(s) (first "
                        f"at {int(pad_bad[0])}) not pointing at entry 0 / "
                        "col 0")
    else:
        live = flat_idx != nnzb
        if flat_idx.min() < 0 or flat_idx.max() > nnzb:
            errs.append(f"{family}: flat_idx outside [0, nnzb={nnzb}] "
                        "(sentinel row is index nnzb)")
        live_counts = np.bincount(seg_row[live], minlength=nbr)
        if not np.array_equal(live_counts, counts):
            bad = int(np.flatnonzero(live_counts != counts)[0])
            errs.append(
                f"{family}: block-row {bad} schedules "
                f"{int(live_counts[bad])} live slot(s) but owns "
                f"{int(counts[bad])} entries — sentinel on a live block "
                "or a dropped slot")
        pad_bad = np.flatnonzero(~live & (flat_col != 0))
        if pad_bad.size:
            errs.append(f"{family}: {pad_bad.size} sentinel slot(s) with "
                        "nonzero flat_col (must DMA block-col 0)")
    if errs:
        return errs

    live_idx = flat_idx[live]
    if not np.array_equal(np.sort(live_idx), np.arange(nnzb)):
        missing = np.setdiff1d(np.arange(nnzb), live_idx)
        dupes = live_idx.size - np.unique(live_idx).size
        errs.append(
            f"{family}: live slots are not a permutation of the {nnzb} "
            f"entries ({missing.size} dropped, {dupes} duplicated) — "
            "coverage contract violated")
        return errs
    if not np.array_equal(row_ids[live_idx], seg_row[live]):
        errs.append(f"{family}: a live slot's entry belongs to a different "
                    "block-row than its schedule segment")
    if not np.array_equal(flat_col[live], col_ids[live_idx]):
        errs.append(f"{family}: flat_col disagrees with the entry list's "
                    "col_ids — the kernel would DMA the wrong B/K panel")
    if flat_col.min() < 0 or flat_col.max() >= nbc:
        errs.append(f"{family}: flat_col outside [0, n_block_cols={nbc})")
    return errs


# ------------------------------------------------------ grid + VMEM checks
def derive_grid(meta, family: str, n: int, bn: int = 512):
    """The Pallas grid the row_loop/fused kernels launch with — the bound
    every schedule index must stay inside."""
    from repro.kernels import ops
    bn_eff = ops._clamp_bn(bn, n)
    n_tiles = -(-n // bn_eff)
    nbr, max_bpr = meta.n_block_rows, meta.max_bpr
    if family == "spmm":
        return (nbr, n_tiles, max_bpr)
    if family == "sddmm":
        return (nbr, max_bpr, n_tiles)
    if family == "attn":
        return (1, nbr, 3, max_bpr)
    raise ValueError(f"unknown family {family!r}")


def estimate_vmem_bytes(meta, family: str, n: int, bn: int = 512) -> int:
    """Working-set estimate for one grid cell (``n`` is N for the
    spmm/sddmm families, head_dim for attn).  ``stream`` is the ``pallas``
    SpMM kernel: its f32 accumulator, output tile and DMA ring
    (``bcsr_spmm.nnz_stream_vmem_bytes``); ``sddmm_stream`` the ``pallas``
    SDDMM kernel: the panel's X, the ring of Y slabs and the ring of output
    blocks over N padded to whole lanes (``bcsr_spmm.
    sddmm_stream_vmem_bytes``; ``bn`` plays no part); the others are the
    double-buffered cells of the shared ``repro.analysis.workspace``
    formulas."""
    from repro.kernels import bcsr_spmm, ops
    bn_eff = ops._clamp_bn(bn, n)
    h, w = meta.block
    if family == "stream":
        return bcsr_spmm.nnz_stream_vmem_bytes(h, w, bn_eff,
                                               meta.n_block_rows, meta.nnzb)
    if family == "sddmm_stream":
        return bcsr_spmm.sddmm_stream_vmem_bytes(
            h, w, -(-n // 128) * 128, meta.n_block_rows, meta.nnzb)
    if family == "attn":
        return (workspace.attn_fused_state_bytes(meta.block, n)
                + workspace.spmm_cell_bytes(meta.block, bn_eff))
    return workspace.spmm_cell_bytes(meta.block, bn_eff) * 2


def _family_for(backend: str, op: str) -> Optional[str]:
    """Which static-schedule family (if any) a resolved backend launches.
    ``None`` = no row_loop-style schedule (nnz_stream / xla / dense)."""
    if op == "attn":
        return "attn" if backend in ("fused", "row_loop") else None
    if backend == "row_loop":
        return op if op in ("spmm", "sddmm") else "spmm"
    return None


def verify_launch(meta, backend: str, *, n: int, bn: int = 512,
                  op: str = "spmm",
                  vmem_budget: int = workspace.DEFAULT_VMEM_BUDGET) -> list:
    """All static checks for one resolved (meta, backend, n, bn, op)
    launch: meta invariants, schedule feasibility for the backend's
    family, and the VMEM budget.  Returns error strings (empty = ok)."""
    errs = list(verify_meta(meta))
    family = _family_for(backend, op)
    if family is not None and meta.max_bpr <= 0:
        errs.append(f"backend {backend!r} (family {family}) needs "
                    "meta.max_bpr > 0; this is a dims-only meta")
    if family is not None and meta.max_bpr > 0:
        sched_len = meta.n_block_rows * meta.max_bpr
        if sched_len < meta.nnzb:
            errs.append(f"schedule length {sched_len} cannot cover "
                        f"nnzb={meta.nnzb}")
        grid = derive_grid(meta, family, n, bn)
        if any(g <= 0 for g in grid):
            errs.append(f"degenerate grid {grid} for family {family}")
    if backend in ("pallas", "row_loop", "fused"):
        vmem_family = family or {"spmm": "stream",
                                 "sddmm": "sddmm_stream"}.get(op, "spmm")
        need = estimate_vmem_bytes(meta, vmem_family, n, bn)
        if need > vmem_budget:
            errs.append(
                f"estimated VMEM working set {need} B exceeds the budget "
                f"{vmem_budget} B for block={meta.block}, bn={bn}, n={n} — "
                "shrink bn or the block")
    return errs


def assert_launch_ok(meta, backend: str, *, n: int, bn: int = 512,
                     op: str = "spmm",
                     vmem_budget: int = workspace.DEFAULT_VMEM_BUDGET):
    """Raise ``LaunchError`` if the resolved launch violates any static
    contract — the ``REPRO_VERIFY_LAUNCH=1`` hook in
    ``ops.resolve_backend``."""
    errs = verify_launch(meta, backend, n=n, bn=bn, op=op,
                         vmem_budget=vmem_budget)
    if errs:
        raise LaunchError(
            f"pre-launch verification failed for backend={backend!r}, "
            f"op={op!r}, n={n}, bn={bn}:\n  - " + "\n  - ".join(errs))


def verify_chunk_schedule(bounds, n: int, *, block=None, bn: int = 512,
                          vmem_budget: int =
                          workspace.DEFAULT_VMEM_BUDGET) -> list:
    """Invariants of an overlap chunk schedule (``dist_spmm
    .chunk_schedule``): the chunks must partition ``[0, n)`` EXACTLY —
    contiguous, strictly ascending, non-empty, no gaps or overlaps — or
    the pipelined concat is not bit-identical to the single-shot panel
    (dropped/duplicated columns).  With ``block`` given, each chunk's
    double-buffered working set must also fit the VMEM budget (chunk
    widths never exceed the full panel, so this catches only schedules
    someone hand-built wrong).  Returns error strings (empty = ok)."""
    errs = []
    try:
        bounds = [(int(lo), int(hi)) for lo, hi in bounds]
    except (TypeError, ValueError):
        return [f"chunk schedule {bounds!r} is not a list of (start, stop)"]
    if not bounds:
        return [f"chunk schedule empty for panel width n={n}"]
    if bounds[0][0] != 0:
        errs.append(f"first chunk starts at {bounds[0][0]}, not 0")
    if bounds[-1][1] != n:
        errs.append(f"last chunk stops at {bounds[-1][1]}, not n={n} — "
                    "the schedule does not cover the panel")
    for i, (lo, hi) in enumerate(bounds):
        if hi <= lo:
            errs.append(f"chunk {i} ({lo}, {hi}) is empty or descending")
    for i in range(1, len(bounds)):
        prev_hi, lo = bounds[i - 1][1], bounds[i][0]
        if lo != prev_hi:
            errs.append(
                f"chunk {i} starts at {lo} but chunk {i - 1} stopped at "
                f"{prev_hi} — {'overlap (columns accumulated twice)' if lo < prev_hi else 'gap (columns dropped)'}")
    if block is not None and not errs:
        from repro.kernels import ops
        for i, (lo, hi) in enumerate(bounds):
            need = workspace.spmm_cell_bytes(
                tuple(block), ops._clamp_bn(bn, hi - lo)) * 2
            if need > vmem_budget:
                errs.append(
                    f"chunk {i} width {hi - lo}: working set {need} B "
                    f"exceeds the VMEM budget {vmem_budget} B")
    return errs


def verify_page_table(mask, seq_len: int, block,
                      resident_pages=None) -> list:
    """Paged-KV page-table invariants (PR 8): the table
    (``models.attention.decode_page_table``) must cover EXACTLY the mask
    support — every stored block-column of the mask BCSR appears exactly
    once among the row's live slots, in ascending order (the
    sequential-fold bitwise contract), with dead slots only in the tail
    — and the placement (``serve.paged_kv.page_placement``) must respect
    the device page budget.  Returns human-readable error strings."""
    from repro.models import attention as A
    from repro.serve import paged_kv as PK
    pages, live, meta = A.decode_page_table(mask, seq_len, block)
    a = A.attention_mask_bcsr(mask, seq_len, block)
    errs = []
    nbr, nbc = meta.n_block_rows, meta.n_block_cols
    if pages.shape != live.shape or \
            pages.shape != (nbr, max(meta.max_bpr, 1)):
        errs.append(f"page-table shape {pages.shape} != "
                    f"({nbr}, {max(meta.max_bpr, 1)})")
        return errs
    if pages.size and (pages.min() < 0 or pages.max() >= nbc):
        errs.append(f"page id out of range [0, {nbc})")
    for i in range(nbr):
        want = np.sort(a.col_ids[a.row_ids == i]).tolist()
        got = pages[i][live[i]].tolist()
        if got != want:
            errs.append(f"row {i}: live pages {got} != mask support {want}"
                        " (coverage must be exact — no gaps, no extras)")
        count = int(live[i].sum())
        if live[i][:count].sum() != count:
            errs.append(f"row {i}: dead slots not a tail suffix")
    pspec = PK.PagePlacementSpec(resident_pages=resident_pages)
    resident = PK.page_placement(mask, seq_len, block, pspec)
    budget = nbc if resident_pages is None else \
        max(0, min(nbc, int(resident_pages)))
    if resident.size != nbc:
        errs.append(f"placement size {resident.size} != n_pages {nbc}")
    if int(resident.sum()) > budget:
        errs.append(f"resident-budget overflow: {int(resident.sum())} "
                    f"pages resident > budget {budget}")
    return errs


def verify_summary(meta, n: int, op: str = "spmm") -> dict:
    """Compact dict for ``launch.dryrun`` reports: meta invariants (and,
    for sharded metas, per-shard checks) re-proved at report time."""
    if hasattr(meta, "shard_metas"):
        errs = verify_sharded_meta(meta)
        checked = f"sharded_meta[{meta.n_shards}]"
    else:
        errs = verify_meta(meta)
        checked = "meta"
    return {"ok": not errs, "checked": checked, "op": op, "n": n,
            "errors": list(errs)}


# ------------------------------------------------------------ structure zoo
@dataclasses.dataclass
class ZooCase:
    """One realized structure: the meta plus the sorted host entry list
    the schedules are built from, and which families apply to it."""
    name: str
    meta: object
    row_ids: np.ndarray
    col_ids: np.ndarray
    families: tuple


def structure_zoo():
    """The metas the acceptance gate runs the verifier over: every
    producer in the repo — ``prepare_sparse_meta`` on random/ragged/
    reordered structures, ``attention_mask_meta`` for each mask family,
    the sharded path, and the deterministic sparse-linear weight
    patterns.  Yields ``ZooCase``s (host numpy only — cheap)."""
    from repro.core import bcsr as bcsr_lib
    from repro.core import sparse_linear as SL
    from repro.core.attention_mask import banded, blockwise_causal, local_global
    from repro.kernels import ops
    from repro.launch import dist_spmm

    def prepared(name, a, families=("spmm", "sddmm"), **kw):
        host, meta = ops._prepare_sparse_host(
            a, reorder=kw.pop("reorder", "identity"),
            reorder_granularity=kw.pop("granularity", "element"),
            tau=0.7, max_candidates=None, n_shards=kw.pop("n_shards", 1))
        return ZooCase(name, meta, host["row_ids"], host["col_ids"],
                       tuple(families))

    yield prepared("rand_uniform_256",
                   bcsr_lib.random_bcsr_exact(0, (256, 256), (16, 16), 64))
    yield prepared("rand_ragged_250x200",
                   bcsr_lib.random_bcsr_exact(1, (250, 200), (16, 16), 40))
    yield prepared("rand_wide_block_32x16",
                   bcsr_lib.random_bcsr_exact(2, (256, 256), (32, 16), 32))
    skew = bcsr_lib.random_bcsr(3, (256, 256), (16, 16), 0.15,
                                fill_density=0.5)
    yield prepared("rand_skew_identity", skew)
    yield prepared("rand_skew_jaccard", skew, reorder="jaccard")

    from repro.models import attention as A
    for mname, spec, seq in (("mask_banded", banded(32), 128),
                             ("mask_local_global", local_global(32, 16), 128),
                             ("mask_causal", blockwise_causal(), 64)):
        a = A.attention_mask_bcsr(spec, seq, (16, 16))
        meta = A.attention_mask_meta(spec, seq, (16, 16))
        yield ZooCase(mname, meta, a.row_ids, a.col_ids,
                      ("spmm", "sddmm", "attn"))

    a = bcsr_lib.random_bcsr_exact(7, (320, 256), (16, 16), 80)
    host, smeta = dist_spmm._prepare_sharded_host(a, 4)
    yield ZooCase("sharded_4", smeta, host["row_ids"], host["col_ids"],
                  ("spmm", "sddmm"))
    # over-budgeted shards: leftover slots pad (row 0, col 0) with
    # DUPLICATE entries, so shard-local max_bpr can exceed n_block_cols —
    # the dims-derived budgets of the model-weight path hit this
    host, smeta = dist_spmm._prepare_sharded_host(a, 4, nnzb_per_shard=60)
    yield ZooCase("sharded_4_padded", smeta, host["row_ids"],
                  host["col_ids"], ("spmm", "sddmm"))

    spec = SL.SparsitySpec(density=0.3, block=(16, 16))
    pat = SL._pattern_for(11, 96, 64, spec)
    yield prepared("linear_d30_64x96", pat, granularity="block_row")


def run_verify(vmem_budget: int = workspace.DEFAULT_VMEM_BUDGET,
               n_values=(64, 512)) -> list:
    """The CLI pass: prove every zoo meta's invariants and every
    applicable schedule's contracts, plus grid/VMEM feasibility at a few
    N values.  Returns ``Finding``s (empty = the tree's structural
    contracts hold)."""
    findings = []

    def emit(case, msgs):
        findings.extend(Finding("launch-verify", f"zoo:{case.name}", 0, m)
                        for m in msgs)

    for case in structure_zoo():
        if hasattr(case.meta, "shard_metas"):
            emit(case, verify_sharded_meta(case.meta))
            metas = list(zip(case.meta.shard_metas,
                             case.row_ids, case.col_ids))
        else:
            emit(case, verify_meta(case.meta))
            metas = [(case.meta, case.row_ids, case.col_ids)]
        for m, rows, cols in metas:
            for family in case.families:
                sched = build_schedule(family, rows, cols, m)
                emit(case, verify_schedule(family, sched[0], sched[1],
                                           rows, cols, m, row_len=sched[2]))
                backend = "fused" if family == "attn" else "row_loop"
                op = family if family != "attn" else "attn"
                for n in n_values:
                    emit(case, [e for e in verify_launch(
                        m, backend, n=n, op=op, vmem_budget=vmem_budget)
                        if e])
        # overlap chunk schedules: the pipelined dispatch is only
        # bit-identical if every (n, n_chunks) schedule partitions the
        # panel exactly and each chunk's working set stays within VMEM
        from repro.launch.dist_spmm import chunk_schedule
        blk = (case.meta.shard_metas[0].block
               if hasattr(case.meta, "shard_metas") else case.meta.block)
        for n in n_values:
            for k in (1, 2, 4):
                emit(case, [f"chunk schedule n={n} k={k}: {e}"
                            for e in verify_chunk_schedule(
                                chunk_schedule(n, k), n, block=blk,
                                vmem_budget=vmem_budget)])

    # paged-KV page tables: exact mask-support coverage + placement
    # budgets, per mask family, with and without an offload budget
    from repro.core.attention_mask import (banded, blockwise_causal,
                                           local_global)
    for mname, spec, seq in (("mask_banded", banded(32), 128),
                             ("mask_local_global", local_global(32, 16), 128),
                             ("mask_causal", blockwise_causal(), 64)):
        for budget in (None, 2):
            findings.extend(
                Finding("launch-verify", f"paged:{mname}", 0, m)
                for m in verify_page_table(spec, seq, (16, 16),
                                           resident_pages=budget))
    return findings
