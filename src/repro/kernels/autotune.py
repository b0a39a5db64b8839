"""Kernel-variant registry + autotuned SpMM dispatch.

SMaT's headline speedups come from matching the kernel schedule and tile
parameters to the matrix's block structure; a single hardcoded
(nnz_stream, bn=512) leaves that on the table.  This module provides:

  * a **registry** of SpMM kernel variants (nnz_stream / xla
    gather-scatter / dense fallback), each with its tunable ``bn``
    candidates and dispatch constraints;
  * a **fingerprint** of a BCSR operand's structure (nnzb, padding ratio,
    blocks-per-row skew, block shape, N-bucket) — the cache key;
  * an **autotuner** that, per fingerprint, either consults the paper's
    performance model (``core.perf_model``, Eq. 1 instantiated with the TPU
    block roofline) for an analytic pick, or runs a timed micro-sweep over
    the registered candidates; decisions are cached in-memory and mirrored
    to a JSON file so benchmarks and serving reuse them across processes.

Wiring: ``ops.spmm(..., backend="auto")`` resolves through
``get_autotuner().pick`` (static info only — trace-safe); explicit
``tune()`` calls (benchmarks, offline warmup) run the measured sweep and
overwrite the analytic entry.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, Iterable, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import bcsr as bcsr_lib
from repro.core import perf_model as pm
from repro.kernels import bcsr_spmm as pk
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# hardcoded pre-registry default — the baseline every pick must beat
DEFAULT_VARIANT = "nnz_stream"
DEFAULT_BN = 512

# VMEM budget for one grid cell's working set (A block + B tile + f32 acc),
# conservative vs the ~128 MiB/core so double buffering always fits.
_VMEM_BUDGET = 8 * 2 ** 20


# ------------------------------------------------------------------ registry
@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One dispatchable kernel schedule.

    ``op`` names the compute family the variant belongs to (``"spmm"`` |
    ``"sddmm"`` | ``"attn"`` — picks never cross families); ``backend`` is the
    ``ops.SpmmConfig.backend`` string the variant lowers to; ``model_time``
    maps (meta, n, bn) -> predicted seconds (paper Eq. 1 terms from
    ``core.perf_model``); ``supported`` gates dispatch on static metadata
    (e.g. attn_fused needs a known max_bpr).
    """
    name: str
    backend: str
    bn_candidates: Tuple[int, ...]
    model_time: Callable[[ops.SparseMeta, int, int], float]
    supported: Callable[[ops.SparseMeta], bool] = lambda meta: True
    description: str = ""
    op: str = "spmm"


_REGISTRY: Dict[str, KernelVariant] = {}


def register_variant(v: KernelVariant) -> KernelVariant:
    if v.name in _REGISTRY:
        raise ValueError(f"variant {v.name!r} already registered")
    _REGISTRY[v.name] = v
    return v


def get_variant(name: str) -> KernelVariant:
    return _REGISTRY[name]


def variant_names(op: str = "spmm") -> Tuple[str, ...]:
    """Registered variant names of one compute family (``op=None`` lists
    every family)."""
    return tuple(n for n, v in _REGISTRY.items() if op is None or v.op == op)


def _bytes_per_el(dtype=jnp.bfloat16) -> int:
    return jnp.dtype(dtype).itemsize


def _n_tiles(n: int, bn: int) -> int:
    return max(-(-n // bn), 1)  # the kernel pads N up to a bn multiple


def _t_nnz_stream(meta: ops.SparseMeta, n: int, bn: int) -> float:
    h, w = meta.block
    return pm.spmm_model_time(meta.nnzb * _n_tiles(n, bn), h, w, bn)


def _t_xla(meta: ops.SparseMeta, n: int, bn: int) -> float:
    # gather + einsum + segment_sum: streams every stored element with
    # blocked (coalesced) access — modeled as CSR traffic at low overhead.
    h, w = meta.block
    return pm.csr_spmm_time(meta.nnzb * h * w, n, gather_overhead=2.0)


def _t_dense(meta: ops.SparseMeta, n: int, bn: int) -> float:
    h, w = meta.block
    return pm.dense_gemm_time(meta.n_block_rows * h, meta.n_block_cols * w, n)


register_variant(KernelVariant(
    name="nnz_stream", backend="pallas", bn_candidates=(128, 256, 512, 1024),
    model_time=_t_nnz_stream,
    description="nonzero-block-streamed Pallas kernel (skew-immune)"))
register_variant(KernelVariant(
    name="xla", backend="xla", bn_candidates=(512,),
    model_time=_t_xla,
    description="pure-jnp gather/segment-sum (shardable oracle path)"))
register_variant(KernelVariant(
    name="dense", backend="dense", bn_candidates=(512,),
    model_time=_t_dense,
    description="materialized dense GEMM (cuBLAS arm; wins at high density)"))


# SDDMM family (ops.sddmm): X @ Y^T sampled at the stored blocks, the
# contraction over N.
def _t_sddmm_stream(meta: ops.SparseMeta, n: int, bn: int) -> float:
    # one grid step per row panel (and contraction chunk, where the panel's
    # X does not fit VMEM whole): each block's Y slab streams once, the
    # panel's X rows and each output block once; bn plays no part
    h, w = meta.block
    chunk, n_chunks = pk.sddmm_chunks(h, w, -(-n // 128) * 128,
                                      meta.n_block_rows)
    n = chunk * n_chunks
    flops = 2.0 * meta.nnzb * h * w * n
    moved = _bytes_per_el() * (meta.nnzb * w * n + meta.n_block_rows * h * n
                               + meta.nnzb * h * w * n_chunks)
    return (max(flops / pm.PEAK_FLOPS_BF16, moved / pm.HBM_BW)
            + n_chunks * _T_INIT)


def _t_sddmm_xla(meta: ops.SparseMeta, n: int, bn: int) -> float:
    h, w = meta.block
    return pm.csr_spmm_time(meta.nnzb * h * w, n, gather_overhead=2.0)


def _t_sddmm_dense(meta: ops.SparseMeta, n: int, bn: int) -> float:
    # the full M x K product, then a block gather (charged as output reread)
    h, w = meta.block
    return pm.dense_gemm_time(meta.n_block_rows * h, n,
                              meta.n_block_cols * w)


register_variant(KernelVariant(
    name="sddmm_stream", backend="pallas", op="sddmm",
    bn_candidates=(512,), model_time=_t_sddmm_stream,
    description="row-panel-streamed Pallas SDDMM (skew-immune)"))
register_variant(KernelVariant(
    name="sddmm_xla", backend="xla", op="sddmm",
    bn_candidates=(512,), model_time=_t_sddmm_xla,
    description="pure-jnp gather/einsum SDDMM (shardable oracle path)"))
register_variant(KernelVariant(
    name="sddmm_dense", backend="dense", op="sddmm",
    bn_candidates=(512,), model_time=_t_sddmm_dense,
    description="dense-masked X Y^T + block gather (near-dense structures)"))


# Attention family (models.attention.block_sparse_attention under
# ``backend="auto"``): fused one-kernel flash-style path vs the composed
# SDDMM -> softmax -> SpMM triple.  These are attention-LEVEL variants —
# their ``backend`` strings ("fused" / "composed") are resolved by
# ``models.attention.resolve_attn_impl``, not by ``ops.SpmmConfig``.
def _t_attn_fused(meta: ops.SparseMeta, n: int, bn: int) -> float:
    # one launch, three passes (max / denom / accumulate) over the static
    # (block-row x slot) schedule — padding slots waste work on short rows,
    # but zero scores/probs HBM traffic between phases
    h, w = meta.block
    n_e = meta.n_block_rows * max(meta.max_bpr, 1) * 3
    return pm.spmm_model_time(n_e, h, w, n)


def _t_attn_composed(meta: ops.SparseMeta, n: int, bn: int) -> float:
    # skew-immune streamed SDDMM + SpMM, plus the materialized [nnzb,h,w]
    # scores/probs tensors crossing HBM twice each between the three
    # launches (write+read for scores, write+read for probs), plus the
    # two extra launch latencies
    h, w = meta.block
    t = _t_sddmm_stream(meta, n, bn) + _t_nnz_stream(meta, n, bn)
    probs_bytes = 4.0 * meta.nnzb * h * w
    return t + 4.0 * probs_bytes / pm.HBM_BW + 2 * 5e-6


register_variant(KernelVariant(
    name="attn_fused", backend="fused", op="attn",
    bn_candidates=(512,), model_time=_t_attn_fused,
    supported=lambda meta: meta.max_bpr > 0,
    description="single-launch fused SDDMM+softmax+SpMM (flash-style, "
                "O(L*d) memory)"))
register_variant(KernelVariant(
    name="attn_composed", backend="composed", op="attn",
    bn_candidates=(512,), model_time=_t_attn_composed,
    description="three-dispatch composed path (materializes scores/probs)"))


# --------------------------------------------------------------- fingerprint
def _pow2_bucket(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 0 else 0


@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """Structure stats that determine the best (variant, bn) — the cache
    key.  Continuous stats are bucketed so near-identical matrices share
    entries (pad to 10%, skew to 25%, N to the next power of two).
    ``reorder`` is part of the key: a permuted matrix has a different
    blocks-per-row skew than its un-permuted twin, so cached picks must
    not alias across reorder schemes.  ``n_shards`` (v3) is part of the
    key too: a shard of a row-partitioned operand (``launch.dist_spmm``)
    has its own stats AND a different execution context (its N-tile shares
    the device with the other shards), so per-shard picks must not alias
    the unsharded twin's entries.  ``max_bpr`` (v4) carries the
    fused attention's schedule bound EXACTLY (not bucketed): reordering
    shrinks it, the fused kernel's static (block-row x slot) grid is
    ``n_block_rows * max_bpr`` long, and two structures whose other stats
    coincide but whose schedule bounds differ must never share a cached
    ``attn_fused`` decision.  ``op`` (v5) names
    the compute family: ``ops.spmm`` and ``ops.sddmm`` dispatch over the
    SAME structures with different optimal schedules (SDDMM contracts
    over N instead of streaming it), so their picks
    must never alias.  v6 adds the ``attn`` family (fused one-kernel
    attention vs the composed triple — a third disjoint pick space over
    the same structures) and bumps the key prefix so v5 caches, which
    predate the family split, are invalidated wholesale rather than
    partially reused.  v7 adds ``n_chunks`` (``nk=``): the overlap depth
    of the communication-pipelined sharded execution
    (``dist_spmm.spmm_sharded(n_chunks=...)``).  It keys the SHARD-COUNT
    decisions (``pick_shards`` — the best S depends on how much of the B
    collective the pipeline can hide), NOT the kernel-variant picks:
    chunking never changes the per-shard kernel launch shape, and variant
    picks stay resolved at the full panel width (``nk=1``) so the chunked
    path dispatches bit-identically to the unchunked one even under
    measured caches."""
    n_block_rows: int
    n_block_cols: int
    block: Tuple[int, int]
    nnzb: int
    pad_bucket: int      # padding_ratio in 10% buckets
    skew_bucket: int     # blocks-per-row cv in 25% buckets
    n_bucket: int        # next pow2 of N
    reorder: str = "identity"
    n_shards: int = 1    # shard count of the partitioned operand (1 = whole)
    max_bpr: int = 0     # fused attention's schedule bound (0 = unknown)
    op: str = "spmm"     # compute family (spmm | sddmm | attn)
    n_chunks: int = 1    # B-panel overlap chunks (shard-count key axis)

    def key(self) -> str:
        h, w = self.block
        return (f"v7|op={self.op}"
                f"|nbr={self.n_block_rows}|nbc={self.n_block_cols}"
                f"|b={h}x{w}|nnzb={self.nnzb}|pad={self.pad_bucket}"
                f"|skew={self.skew_bucket}|n={self.n_bucket}"
                f"|ro={self.reorder}|ns={self.n_shards}|mb={self.max_bpr}"
                f"|nk={self.n_chunks}")


def _make_fingerprint(nbr: int, nbc: int, block, nnzb: int,
                      pad_pct: int, cv_pct: int, n: int,
                      reorder: str = "identity",
                      n_shards: int = 1, max_bpr: int = 0,
                      op: str = "spmm", n_chunks: int = 1) -> Fingerprint:
    """Single bucketing site for both fingerprint paths — the meta-side and
    BCSR-side keys must agree bit-for-bit or cached picks stop matching."""
    return Fingerprint(
        n_block_rows=nbr, n_block_cols=nbc, block=tuple(block), nnzb=nnzb,
        pad_bucket=pad_pct // 10, skew_bucket=cv_pct // 25,
        n_bucket=_pow2_bucket(n), reorder=reorder, n_shards=n_shards,
        max_bpr=max_bpr, op=op, n_chunks=n_chunks)


def fingerprint(meta: ops.SparseMeta, n: int,
                op: str = "spmm", n_chunks: int = 1) -> Fingerprint:
    """Fingerprint from the static meta ``prepare_sparse`` built (or a
    per-shard meta from ``dist_spmm.prepare_sharded`` — its ``n_shards``
    and ``max_bpr`` ride into the v7 key).  ``op`` selects the compute
    family's key space (``spmm`` | ``sddmm`` | ``attn``); ``n_chunks``
    (``nk=``) is the overlap depth — pass it only for shard-count
    decisions, kernel-variant picks keep the default 1."""
    return _make_fingerprint(meta.n_block_rows, meta.n_block_cols,
                             meta.block, meta.nnzb,
                             meta.padding_ratio_pct, meta.bpr_cv_pct, n,
                             reorder=meta.reorder, n_shards=meta.n_shards,
                             max_bpr=meta.max_bpr, op=op, n_chunks=n_chunks)


def fingerprint_bcsr(a: bcsr_lib.BCSR, n: int,
                     reorder: str = "identity",
                     op: str = "spmm") -> Fingerprint:
    """Fingerprint from a host BCSR — matches ``fingerprint`` of the meta
    ``prepare_sparse`` would build (same row padding applied first; both
    sides go through ``BCSR.dispatch_stats`` + ``_make_fingerprint``).
    ``reorder`` names the scheme that PRODUCED this matrix's structure —
    pass the same value given to ``prepare_sparse``; the matrix itself is
    not re-permuted here."""
    a_p = a.ensure_nonempty_rows()
    max_bpr, pad_pct, cv_pct = a_p.dispatch_stats()
    return _make_fingerprint(a_p.n_block_rows, a_p.n_block_cols, a_p.block,
                             a_p.nnzb, pad_pct, cv_pct, n, reorder=reorder,
                             max_bpr=max_bpr, op=op)


# -------------------------------------------------------------------- choice
@dataclasses.dataclass(frozen=True)
class KernelChoice:
    variant: str
    bn: int
    source: str = "analytic"    # analytic | measured | default
    predicted_us: float = 0.0

    @property
    def backend(self) -> str:
        return get_variant(self.variant).backend

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "KernelChoice":
        return KernelChoice(variant=d["variant"], bn=int(d["bn"]),
                            source=d.get("source", "analytic"),
                            predicted_us=float(d.get("predicted_us", 0.0)))


def default_variant(op: str = "spmm") -> str:
    """The hardcoded pre-registry default of one compute family — the
    baseline every pick must beat.  For ``attn`` that is the composed
    triple: the fused kernel must WIN the model comparison to dispatch."""
    if op == "attn":
        return "attn_composed"
    return DEFAULT_VARIANT if op == "spmm" else "sddmm_stream"


def default_choice(op: str = "spmm") -> KernelChoice:
    return KernelChoice(default_variant(op), DEFAULT_BN, source="default")


def pick_bn(meta: ops.SparseMeta, n: int,
            candidates: Iterable[int]) -> int:
    """Largest candidate whose per-cell working set fits the VMEM budget
    (wider N-tiles amortize the A-block stream; the budget caps them)."""
    h, w = meta.block
    feasible = []
    for bn in candidates:
        working = (h * w + w * bn) * 2 + (h * bn) * 4  # bf16 in, f32 acc
        if working * 2 <= _VMEM_BUDGET:                # double-buffered
            feasible.append(bn)
    if not feasible:
        feasible = [min(candidates)]
    # no point tiling wider than (padded) N
    fit_n = [bn for bn in feasible if bn <= max(n, min(feasible))]
    return max(fit_n or feasible)


def analytic_choice(meta: ops.SparseMeta, n: int,
                    op: str = "spmm") -> KernelChoice:
    """Model-based pick: paper Eq. 1 per variant of the ``op`` family,
    minimum predicted time."""
    best: Optional[Tuple[float, str, int]] = None
    for v in _REGISTRY.values():
        if v.op != op or not v.supported(meta):
            continue
        bn = pick_bn(meta, n, v.bn_candidates)
        t = float(v.model_time(meta, n, bn))
        if best is None or t < best[0]:
            best = (t, v.name, bn)
    if best is None:  # every variant gated off — keep the hardcoded default
        return default_choice(op)
    t, name, bn = best
    return KernelChoice(name, bn, source="analytic", predicted_us=t * 1e6)


# ----------------------------------------------------------- shard-count axis
# Candidate shard counts for the self-sizing distributed path
# (``dist_spmm``): powers of two up to the mesh/row limit, 1 = unsharded.
SHARD_CANDIDATES = (1, 2, 4, 8)

_T_INIT = 5e-6        # per-launch latency (matches pm.spmm_model_time)
_T_SHARD_SYNC = 5e-7  # cross-shard coordination cost per shard doubling


@dataclasses.dataclass(frozen=True)
class ShardChoice:
    """A cached shard-count decision (the S analogue of KernelChoice)."""
    n_shards: int
    source: str = "analytic"    # analytic | measured
    predicted_us: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "ShardChoice":
        return ShardChoice(n_shards=int(d["n_shards"]),
                           source=d.get("source", "analytic"),
                           predicted_us=float(d.get("predicted_us", 0.0)))


def shard_candidates(max_shards: int, n_block_rows: int) -> Tuple[int, ...]:
    """The S values ``pick_shards`` considers: ``SHARD_CANDIDATES`` capped
    by the mesh size AND the block-row count (a shard with zero row slots
    is pure overhead)."""
    cap = max(min(int(max_shards), max(int(n_block_rows), 1)), 1)
    cands = tuple(s for s in SHARD_CANDIDATES if s <= cap)
    return cands or (1,)


def _pipeline_time(t_comp: float, t_coll: float, n_chunks: int) -> float:
    """Total time of a ``k``-stage software pipeline that issues the
    collective for chunk ``i+1`` before the matmul over chunk ``i``: only
    the first chunk's collective is exposed; every later stage runs at the
    rate of the slower leg."""
    k = max(int(n_chunks), 1)
    return t_coll / k + t_comp / k + (k - 1) / k * max(t_comp, t_coll)


def analytic_shard_choice(meta: ops.SparseMeta, n: int, *,
                          max_shards: int = 8, n_chunks: int = 1,
                          op: str = "spmm") -> ShardChoice:
    """Model-based shard count for the partitioned execution path.

    The S=1 arm is the plain paper Eq. 1 (no collective: a single device
    already holds B).  For S>1 the per-shard work is the balanced LPT load
    (``ceil(nnzb/S)`` entries plus one virtual-row sentinel per row slot),
    the B broadcast crosses ICI once, and the two legs compose through the
    ``n_chunks``-deep overlap pipeline — so deeper chunking makes larger S
    win sooner, which is exactly why ``nk=`` is part of the cache key.
    A ``log2(S)`` coordination term keeps the model from racing to the
    mesh cap on structures whose compute no longer dominates.  Ties go to
    the SMALLER S (fewer moving parts at equal predicted time)."""
    h, w = meta.block
    nbr = max(meta.n_block_rows, 1)
    bn = pick_bn(meta, n, get_variant(default_variant("spmm")).bn_candidates)
    tiles = _n_tiles(n, bn)
    _, _, t_e = pm.block_mma_time(h, w, bn)
    t_coll = float(meta.shape[1]) * n * _bytes_per_el() / pm.ICI_BW
    best: Optional[Tuple[float, int]] = None
    for s in shard_candidates(max_shards, nbr):
        if s == 1:
            t = pm.spmm_model_time(meta.nnzb * tiles, h, w, bn)
        else:
            load = -(-meta.nnzb // s) + -(-nbr // s)
            t_comp = t_e * load * tiles
            t = (_T_INIT + _T_SHARD_SYNC * (s.bit_length() - 1)
                 + _pipeline_time(t_comp, t_coll, n_chunks))
        if best is None or t < best[0]:
            best = (t, s)
    t, s = best
    return ShardChoice(s, source="analytic", predicted_us=t * 1e6)


def shard_entry_key(fp: Fingerprint, max_shards: int) -> str:
    """Cache key of a shard-count decision: the mesh cap prefixed onto the
    structure's v7 fingerprint (which carries ``nk=``), so decisions made
    for different device budgets or overlap depths never alias."""
    return f"shards|max={int(max_shards)}|{fp.key()}"


# ------------------------------------------------------------ timed sweeps
# a sweep's timings: seconds per candidate, or "failed: <ExceptionType>"
Timings = Dict[str, Union[float, str]]


def time_candidate(timings: Timings, label: str, fn, *operands,
                   warmup: int, iters: int, **where) -> None:
    """Time one sweep candidate into ``timings[label]`` (median seconds).

    A candidate that raises is recorded, not skipped: ``timings[label]``
    becomes ``"failed: <ExceptionType>"`` and an
    ``autotune.candidate_failed`` event names it with the message."""
    try:
        timings[label] = obs_metrics.timeit(
            fn, *operands, warmup=max(warmup, 1), iters=iters)
    except Exception as e:  # recorded in the sweep result, not skipped
        timings[label] = f"failed: {type(e).__name__}"
        obs_trace.event("autotune.candidate_failed", candidate=label,
                        error=type(e).__name__, message=str(e)[:500],
                        **where)
        obs_metrics.counter("autotune.candidate_failed").inc()


def measured_winner(timings: Timings, default_label: str) -> str:
    """Fastest measured label; the default wins ties within noise (2%).
    Raises when the default itself failed: a pick nothing measured
    against the baseline is never cached as ``measured``."""
    measured = {k: v for k, v in timings.items() if not isinstance(v, str)}
    if default_label not in measured:
        raise RuntimeError(
            f"autotune sweep: the default candidate {default_label} did "
            f"not run ({timings.get(default_label, 'not swept')}); "
            f"timings: {timings}")
    best = min(measured, key=measured.get)
    if measured[default_label] <= measured[best] * 1.02:
        best = default_label
    return best


# ----------------------------------------------------------------- autotuner
class Autotuner:
    """Fingerprint -> KernelChoice cache with analytic and measured fills.

    ``cache_path`` (or the ``REPRO_AUTOTUNE_CACHE`` environment variable —
    set it to a writable ``<path>.json`` to share tuned picks across
    processes, e.g. from an offline benchmark run into a serving process)
    mirrors the table to JSON; loading tolerates a missing or corrupt file
    (starts empty), saving is atomic (tmp + rename).  With neither set the
    cache is in-memory only.

    A cache MISS never blocks dispatch: ``pick`` falls back to the
    analytic perf-model choice (paper Eq. 1), so ``backend="auto"`` is
    always trace-safe.  Timed sweeps only run via explicit ``tune()`` /
    ``dist_spmm.tune_shards`` calls.

    >>> import numpy as np
    >>> from repro.core import bcsr as bcsr_lib
    >>> from repro.kernels import autotune, ops
    >>> a = bcsr_lib.random_bcsr_exact(0, (256, 256), (16, 16), nnzb=64)
    >>> meta = ops.prepare_sparse_meta(a)
    >>> tuner = autotune.Autotuner()          # in-memory (no cache file)
    >>> choice = tuner.pick(meta, n=128)
    >>> choice.variant in autotune.variant_names()
    True
    >>> tuner.pick(meta, n=128) is choice     # cached under the v7 key
    True
    """

    def __init__(self, cache_path: Optional[str] = None):
        self.cache_path = cache_path or os.environ.get(
            "REPRO_AUTOTUNE_CACHE") or None
        self._mem: Dict[str, KernelChoice] = {}
        self._shards: Dict[str, ShardChoice] = {}
        if self.cache_path:
            self.load()

    # ------------------------------------------------------------- storage
    def load(self) -> None:
        try:
            with open(self.cache_path) as f:
                payload = json.load(f)
            for k, d in payload.get("entries", {}).items():
                if d.get("variant") in _REGISTRY:
                    self._mem[k] = KernelChoice.from_dict(d)
            for k, d in payload.get("shard_entries", {}).items():
                self._shards[k] = ShardChoice.from_dict(d)
        except (OSError, ValueError, KeyError, AttributeError, TypeError):
            pass  # absent/corrupt/wrong-shape cache -> start empty

    def save(self) -> None:
        if not self.cache_path:
            return
        payload = {"version": 1,
                   "entries": {k: c.to_dict() for k, c in self._mem.items()},
                   "shard_entries": {k: c.to_dict()
                                     for k, c in self._shards.items()}}
        tmp = f"{self.cache_path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self.cache_path)),
                        exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.cache_path)
        except OSError:
            pass  # read-only FS: in-memory cache still works

    # -------------------------------------------------------------- lookup
    def get(self, fp: Fingerprint) -> Optional[KernelChoice]:
        return self._mem.get(fp.key())

    def put(self, fp: Fingerprint, choice: KernelChoice,
            persist: bool = True) -> None:
        self._mem[fp.key()] = choice
        if persist:
            self.save()

    def get_shards(self, fp: Fingerprint,
                   max_shards: int) -> Optional[ShardChoice]:
        return self._shards.get(shard_entry_key(fp, max_shards))

    def put_shards(self, fp: Fingerprint, max_shards: int,
                   choice: ShardChoice, persist: bool = True) -> None:
        self._shards[shard_entry_key(fp, max_shards)] = choice
        if persist:
            self.save()

    def pick_shards(self, meta: ops.SparseMeta, n: int, *,
                    max_shards: int = 8, n_chunks: int = 1,
                    op: str = "spmm") -> ShardChoice:
        """Cached shard count for this structure, analytic on a miss.

        The S analogue of ``pick``: static info only, trace-safe, never
        blocks dispatch.  Decisions key on
        ``shards|max=<mesh cap>|<v7 fingerprint>`` — the fingerprint
        carries ``nk=n_chunks``, so the same structure planned with and
        without overlap resolves (and caches) independently.  Measured
        winners land here via ``dist_spmm.tune_shard_count``."""
        fp = fingerprint(meta, n, op=op, n_chunks=n_chunks)
        hit = self.get_shards(fp, max_shards)
        if hit is not None:
            obs_trace.event("autotune.pick_shards", key=fp.key(),
                            max_shards=max_shards, n_shards=hit.n_shards,
                            source=hit.source, cache_hit=True)
            obs_metrics.counter("autotune.cache_hit", kind="shards").inc()
            return hit
        choice = analytic_shard_choice(meta, n, max_shards=max_shards,
                                       n_chunks=n_chunks, op=op)
        # cache in memory only — analytic resolutions are cheap to
        # recompute and may run inside first-trace paths (same policy as
        # pick())
        self._shards[shard_entry_key(fp, max_shards)] = choice
        obs_trace.event("autotune.pick_shards", key=fp.key(),
                        max_shards=max_shards, n_shards=choice.n_shards,
                        source=choice.source, cache_hit=False)
        obs_metrics.counter("autotune.cache_miss", kind="shards").inc()
        return choice

    def __len__(self) -> int:
        return len(self._mem)

    def pick(self, meta: ops.SparseMeta, n: int,
             op: str = "spmm") -> KernelChoice:
        """Cached choice for this structure, analytic on a miss.  Static
        info only — safe inside jit traces (``backend="auto"`` path).
        ``op`` selects the variant family (``spmm`` | ``sddmm`` | ``attn``)
        and its disjoint v7 key space."""
        fp = fingerprint(meta, n, op=op)
        hit = self.get(fp)
        if hit is not None:
            obs_trace.event("autotune.pick", key=fp.key(), op=op,
                            variant=hit.variant, bn=hit.bn,
                            source=hit.source, cache_hit=True)
            obs_metrics.counter("autotune.cache_hit", op=op).inc()
            return hit
        choice = analytic_choice(meta, n, op=op)
        # cache (no disk write: analytic picks are cheap to recompute and
        # pick() may run inside latency-sensitive first-trace paths)
        self.put(fp, choice, persist=False)
        obs_trace.event("autotune.pick", key=fp.key(), op=op,
                        variant=choice.variant, bn=choice.bn,
                        source=choice.source, cache_hit=False)
        obs_metrics.counter("autotune.cache_miss", op=op).inc()
        return choice

    # ------------------------------------------------------------- tuning
    def tune(self, a: bcsr_lib.BCSR, n: int, *, dtype=jnp.float32,
             interpret: bool = False,
             variants: Optional[Iterable[str]] = None,
             warmup: int = 1, iters: int = 3, rng_seed: int = 0,
             reorder: str = "identity",
             reorder_granularity: str = "element",
             n_shards: int = 8,
             op: str = "spmm") -> Tuple[KernelChoice, Timings]:
        """Timed micro-sweep over the ``op`` family's (variant, bn)
        candidates.

        Always measures the family's hardcoded default (``nnz_stream`` /
        ``sddmm_stream``, bn=512) so the winner is never slower than it;
        returns (choice, {candidate: sec}).  A candidate that raises is
        recorded as ``"failed: <ExceptionType>"`` in those timings; if the
        default fails, ``tune`` raises and caches nothing.  The winner is
        cached (and persisted) under the matrix's v7 ``op=``-scoped
        fingerprint.  ``interpret=True`` times the Pallas interpreter (the
        CPU test path) — never what a chip runs.
        ``reorder`` mirrors the ``prepare_sparse`` arguments so the sweep
        measures (and the fingerprint matches) the permuted structure the
        apply path will actually dispatch on.  For ``op="sddmm"`` the
        timed call is ``ops.sddmm(arrays, meta, x, y)`` with dense
        operands ``x [M, n]`` / ``y [K, n]`` (n = the contraction width).
        """
        arrays, meta = ops.prepare_sparse(
            a, dtype=dtype, reorder=reorder,
            reorder_granularity=reorder_granularity, n_shards=n_shards)
        fp = fingerprint(meta, n, op=op)
        rng = np.random.default_rng(rng_seed)
        if op == "sddmm":
            x = jnp.asarray(rng.standard_normal((meta.shape[0], n)),
                            dtype=dtype)
            y = jnp.asarray(rng.standard_normal((meta.shape[1], n)),
                            dtype=dtype)

            def _mk_fn(backend, bn):
                return jax.jit(lambda xx, yy: ops.sddmm(
                    arrays, meta, xx, yy, backend=backend, bn=bn,
                    interpret=interpret))
            operands = (x, y)
        else:
            b = jnp.asarray(rng.standard_normal((meta.shape[1], n)),
                            dtype=dtype)

            def _mk_fn(backend, bn):
                return jax.jit(lambda bb: ops.spmm(
                    arrays, meta, bb, backend=backend, bn=bn,
                    interpret=interpret))
            operands = (b,)

        names = tuple(variants) if variants else variant_names(op)
        cand: Dict[str, Tuple[str, int]] = {}
        for name in names:
            v = get_variant(name)
            if v.op != op or not v.supported(meta):
                continue
            bns = {pick_bn(meta, n, v.bn_candidates)}
            bns.update(bn for bn in v.bn_candidates if bn <= max(n, 128))
            for bn in sorted(bns):
                cand[f"{name}/bn{bn}"] = (name, bn)
        dv = default_variant(op)
        cand.setdefault(f"{dv}/bn{DEFAULT_BN}", (dv, DEFAULT_BN))

        timings: Timings = {}
        with obs_trace.span("autotune.tune", key=fp.key(), op=op,
                            n_candidates=len(cand)):
            for label, (name, bn) in cand.items():
                time_candidate(
                    timings, label, _mk_fn(get_variant(name).backend, bn),
                    *operands, warmup=warmup, iters=iters, key=fp.key(),
                    op=op)

        best_label = measured_winner(timings, f"{dv}/bn{DEFAULT_BN}")
        name, bn = cand[best_label]
        choice = KernelChoice(name, bn, source="measured",
                              predicted_us=timings[best_label] * 1e6)
        self.put(fp, choice, persist=True)
        obs_trace.event("autotune.tuned", key=fp.key(), op=op,
                        variant=choice.variant, bn=choice.bn,
                        n_candidates=len(timings),
                        n_failed=sum(isinstance(v, str)
                                     for v in timings.values()))
        obs_metrics.counter("autotune.tuned", op=op).inc()
        return choice, timings


# ---------------------------------------------------------------- singleton
_DEFAULT_TUNER: Optional[Autotuner] = None


def get_autotuner() -> Autotuner:
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = Autotuner()
    return _DEFAULT_TUNER


def set_autotuner(tuner: Optional[Autotuner]) -> None:
    """Swap the process-wide tuner (tests; serving with a shared cache)."""
    global _DEFAULT_TUNER
    _DEFAULT_TUNER = tuner
