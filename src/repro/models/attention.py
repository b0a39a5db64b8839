"""Block-sparse attention — the SDDMM/SpMM pair as a sequence-mixing layer.

The paper's ops power two workloads: sparse *weights* (the FFN path that
has been in the repo since PR 1) and sparse *interactions* — attention
whose score matrix is only evaluated on a static block mask.  This module
builds the second one from the public kernel ops:

    scores = ops.sddmm(mask, Q, K)        # Q K^T sampled at stored blocks
    probs  = masked block softmax         # per query row, stored keys only
    ctx    = ops.spmm(mask<-probs, V)     # probs @ V over the same structure

Gradients need no extra code: SpMM and SDDMM are mutual duals (each op's
custom VJP calls the other), so d(ctx)/d{Q,K,V} bounces between the two
Pallas kernels exactly like the dense math would between its two GEMMs.

Since PR 6 the three dispatches also exist as ONE kernel:
``kernels.bcsr_attn.bcsr_attn_fused`` recomputes the score blocks inside
a single Pallas launch and folds them into per-query-block running
(max, sum, accumulator) state — O(L * d) memory, no materialized scores
or probs.  ``backend="auto"`` arbitrates fused vs composed through the
``op="attn"`` autotune family (v6 fingerprints — fused and composed
picks never alias); ``backend="fused"`` forces it.  The fused forward is
bit-for-bit equal to the composed path in f32, which lets the backward
stay on the composed dual-VJP route (no fused backward).

Masks are STATIC (a pure function of ``(mask_spec, seq_len, block)``), so
the whole PR-4 static-metadata pipeline applies: ``attention_mask_meta``
memoizes the true structure meta — nnzb, ``max_bpr``, skew — without
building arrays, ``backend="auto"`` resolves the SDDMM and SpMM variants
per layer from the v6 fingerprints, and scanned layer stacks merge their
per-layer metas with ``core.sparse_linear.merge_sparse_metas``.  The index
arrays themselves are trace-time constants, never params — a mask has no
gradient.

Wired end-to-end: ``ModelConfig.attn_sparsity`` switches
``models.layers.attention``'s train/prefill path onto this module (decode
applies the same mask spec as a positional bias, so serving stays
consistent with training); ``launch.dryrun`` prints the mask nnzb and the
auto picks; ``AttnSparsitySpec(shards=S)`` row-shards the score structure
through ``launch.dist_spmm`` (shard_map under a compatible mesh, identical
in-process math otherwise).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bcsr as bcsr_lib
# the spec/builder leaf lives in core (configs imports it too — the layer
# map stays one-directional); this module is the user-facing namespace,
# so re-export the whole surface:
from repro.core.attention_mask import (NEG_INF, AttnMaskSpec,  # noqa: F401
                                       AttnSparsitySpec, banded,
                                       blockwise_causal, local_global,
                                       mask_allowed)
from repro.core.sparse_linear import merge_sparse_metas
from repro.kernels import bcsr_attn, ops


def decode_mask_bias(spec: AttnMaskSpec, q_pos: jnp.ndarray,
                     k_pos: jnp.ndarray) -> jnp.ndarray:
    """Additive decode-step bias ``[..., Lq, Sk]`` applying the SAME mask
    the block-sparse train/prefill path realizes — what keeps a served
    model consistent with how it was trained."""
    return jnp.where(mask_allowed(spec, q_pos, k_pos), 0.0, NEG_INF)


@functools.lru_cache(maxsize=None)
def decode_page_table(spec: AttnMaskSpec, seq_len: int,
                      block: Tuple[int, int]):
    """Serving page table of the mask — literally the mask BCSR reshaped
    to a ``[n_block_rows, max_bpr]`` slot grid (the page-table-as-BCSR
    contract of ``serve.paged_kv``): row ``i`` lists, in ascending key
    order, the ids of every KV page (block-column of width ``block[1]``)
    that queries in block-row ``i`` can ever touch under ``spec``;
    ``live`` marks real slots (rows with fewer than ``max_bpr`` mask
    blocks pad with dead slots that gather page 0 and are masked out).

    Host numpy constants, memoized like the other mask pipelines —
    trace-safe to close over in a jitted decode step.  Returns
    ``(pages, live, meta)``.

    >>> from repro.models import attention as A
    >>> pages, live, meta = A.decode_page_table(A.banded(32), 64, (16, 16))
    >>> pages.shape == live.shape == (4, meta.max_bpr)
    True
    >>> pages[3][live[3]].tolist()      # block-row 3 touches pages 1..3
    [1, 2, 3]
    """
    a = attention_mask_bcsr(spec, seq_len, block)
    meta = attention_mask_meta(spec, seq_len, block)
    nbr = meta.n_block_rows
    slots = max(meta.max_bpr, 1)
    pages = np.zeros((nbr, slots), np.int32)
    live = np.zeros((nbr, slots), bool)
    counts = np.bincount(a.row_ids, minlength=nbr)
    rowptr = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(a.row_ids.shape[0]) - rowptr[a.row_ids]
    pages[a.row_ids, slot] = a.col_ids          # ascending within each row
    live[a.row_ids, slot] = True
    pages.setflags(write=False)
    live.setflags(write=False)
    return pages, live, meta


# ======================================================== mask BCSR pipeline
@functools.lru_cache(maxsize=None)
def attention_mask_bcsr(spec: AttnMaskSpec, seq_len: int,
                        block: Tuple[int, int]) -> bcsr_lib.BCSR:
    """Host BCSR of the element mask (vals are 0/1 f32; blocks with any
    allowed element are stored).  Memoized: the mask is a pure function of
    ``(spec, seq_len, block)`` — the attention analogue of the weight
    pipeline's deterministic ``(seed, dims, spec)`` patterns.

    Built one block-row strip at a time (peak O(h * L) host memory) — a
    dense [L, L] mask would be multi-GiB at the 32k prefill cell — with
    output identical to ``from_dense(mask_allowed(...))`` on the full
    dense mask (entries row-major by (block-row, block-col))."""
    h, w = block
    nbr = -(-seq_len // h)
    nbc = -(-seq_len // w)
    k_pos = np.arange(nbc * w)
    k_valid = k_pos < seq_len
    rows, cols, vals = [], [], []
    for i in range(nbr):
        q_pos = np.arange(i * h, (i + 1) * h)
        strip = mask_allowed(spec, q_pos, k_pos)
        strip &= k_valid[None, :] & (q_pos < seq_len)[:, None]
        blocks = strip.reshape(h, nbc, w).transpose(1, 0, 2)  # [nbc, h, w]
        nz = np.flatnonzero(blocks.any(axis=(1, 2)))
        rows.append(np.full(nz.size, i, np.int32))
        cols.append(nz.astype(np.int32))
        vals.append(blocks[nz].astype(np.float32))
    row_ids = np.concatenate(rows)
    col_ids = np.concatenate(cols)
    vals = np.concatenate(vals) if row_ids.size else \
        np.zeros((0, h, w), np.float32)
    return bcsr_lib.BCSR(vals, col_ids, row_ids,
                         bcsr_lib.rowptr_from_rows(row_ids, nbr),
                         (seq_len, seq_len), (h, w))


@functools.lru_cache(maxsize=None)
def attention_mask_meta(spec: AttnMaskSpec, seq_len: int,
                        block: Tuple[int, int]) -> ops.SparseMeta:
    """TRUE structure meta of the mask — ``prepare_sparse_meta`` on the
    deterministic mask BCSR, memoized.  This is what ``backend="auto"``
    fingerprints (v6: the ``op=attn`` fused-vs-composed pick plus the
    composed path's ``op=sddmm`` / ``op=spmm`` picks) and what
    ``launch.dryrun`` reports, with no arrays built."""
    return ops.prepare_sparse_meta(attention_mask_bcsr(spec, seq_len, block))


@functools.lru_cache(maxsize=None)
def attention_mask_arrays(spec: AttnMaskSpec, seq_len: int,
                          block: Tuple[int, int]
                          ) -> Tuple[ops.SparseArrays, ops.SparseMeta]:
    """Arrays + meta of the mask structure.  The arrays are HOST (numpy)
    constants — index structure and 0/1 element weights.  They are not
    params, carry no gradient, and embed as trace-time constants in
    whatever jit/scan body touches them; keeping them numpy (instead of
    device arrays) makes the memoized value safe to build lazily inside a
    trace and to share across traces."""
    host, meta = ops._prepare_sparse_host(
        attention_mask_bcsr(spec, seq_len, block), reorder="identity",
        reorder_granularity="element", tau=0.7, max_candidates=None,
        n_shards=1)
    assert meta == attention_mask_meta(spec, seq_len, block)
    arrays = ops.SparseArrays(
        vals=host["vals"].astype(np.float32),
        row_ids=host["row_ids"].astype(np.int32),
        col_ids=host["col_ids"].astype(np.int32),
        real_mask=host["real_mask"],
        t_perm=host["t_perm"].astype(np.int32),
        t_row_ids=host["t_row_ids"].astype(np.int32),
        t_col_ids=host["t_col_ids"].astype(np.int32),
        row_perm=host["row_perm"].astype(np.int32),
        inv_perm=host["inv_perm"].astype(np.int32))
    return arrays, meta


def merged_attention_meta(specs, seq_len: int,
                          block: Tuple[int, int]) -> ops.SparseMeta:
    """One static meta covering every layer of a scanned stack — the
    attention twin of ``models.layers.mlp_sparse_metas``: per-spec metas
    merge conservatively (``merge_sparse_metas``: stats take the stack
    max), so a single traced body dispatches correctly for all layers."""
    return merge_sparse_metas(
        [attention_mask_meta(s, seq_len, block) for s in specs])


@functools.lru_cache(maxsize=None)
def _mask_sharded(spec: AttnMaskSpec, seq_len: int, block: Tuple[int, int],
                  n_shards: int):
    """Row-partitioned view of the mask structure (``launch.dist_spmm``):
    the context SpMM's score operand split over block-rows with the LPT
    balancer.  The flat probs computed by the SDDMM drop into the
    partition's ``vals`` slot untouched — both sides are built from the
    same padded host BCSR, so the global entry order is shared."""
    from repro.launch import dist_spmm  # local: layering
    a = attention_mask_bcsr(spec, seq_len, block)
    host, smeta = dist_spmm._prepare_sharded_host(a, n_shards)
    _, meta = attention_mask_arrays(spec, seq_len, block)
    if smeta.nnzb != meta.nnzb:   # same padded entry list by construction
        raise AssertionError(
            f"sharded/unsharded mask entry counts diverged: "
            f"{smeta.nnzb} vs {meta.nnzb}")
    # host (numpy) constants, like attention_mask_arrays — trace-safe
    sharr = dist_spmm.ShardedArrays(
        vals=host["vals"].astype(np.float32),
        src_index=host["src_index"].astype(np.int32),
        row_ids=host["row_ids"].astype(np.int32),
        col_ids=host["col_ids"].astype(np.int32),
        real_mask=host["real_mask"],
        t_perm=host["t_perm"].astype(np.int32),
        t_row_ids=host["t_row_ids"].astype(np.int32),
        t_col_ids=host["t_col_ids"].astype(np.int32),
        gather_rows=host["gather_rows"].astype(np.int32))
    return sharr, smeta


# ============================================================= sparse layer
def block_softmax(scores: jnp.ndarray, elem_mask: jnp.ndarray,
                  row_ids: jnp.ndarray, n_block_rows: int,
                  cap: Optional[float] = None) -> jnp.ndarray:
    """Masked softmax over a BCSR score matrix, per GLOBAL query row.

    scores     [nnzb, h, w] f32 logits (already scaled)
    elem_mask  [nnzb, h, w] bool — valid (stored AND allowed) elements
    row_ids    [nnzb] block-row of each block
    returns    [nnzb, h, w] probabilities; masked elements are exactly 0,
               each valid query row sums to 1 across its stored blocks.
    """
    if cap is not None:
        scores = cap * jnp.tanh(scores / cap)
    logits = jnp.where(elem_mask, scores, NEG_INF)
    blk_max = jnp.max(logits, axis=2)                       # [nnzb, h]
    row_max = jax.ops.segment_max(blk_max, row_ids,
                                  num_segments=n_block_rows)  # [nbr, h]
    row_max = jnp.maximum(row_max, -1e30)   # rows with no valid element
    z = jnp.exp(logits - row_max[row_ids][:, :, None])
    z = jnp.where(elem_mask, z, 0.0)
    denom = jax.ops.segment_sum(z.sum(axis=2), row_ids,
                                num_segments=n_block_rows)    # [nbr, h]
    denom = jnp.maximum(denom, 1e-30)
    return z / denom[row_ids][:, :, None]


def _context_spmm(probs: jnp.ndarray, arrays: ops.SparseArrays,
                  meta: ops.SparseMeta, v: jnp.ndarray,
                  spec: AttnSparsitySpec) -> jnp.ndarray:
    """ctx = probs @ V over the mask structure — unsharded, or through the
    ``dist_spmm`` row partition when ``spec.shards > 0``."""
    if spec.shards > 0:
        from repro.launch import dist_spmm  # local: layering
        sharr, smeta = _mask_sharded(spec.mask, meta.shape[0], meta.block,
                                     spec.shards)
        mesh = dist_spmm.current_spmm_mesh()
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes.get(dist_spmm.AXIS_ROW) != spec.shards:
                mesh = None     # incompatible ambient mesh: local fallback
        return dist_spmm.spmm_sharded(
            sharr._replace(vals=probs), smeta, v, backend=spec.backend,
            bn=spec.bn, interpret=spec.interpret, mesh=mesh)
    return ops.spmm(arrays._replace(vals=probs), meta, v,
                    backend=spec.backend, bn=spec.bn,
                    interpret=spec.interpret)


def _composed_spec(spec: AttnSparsitySpec) -> AttnSparsitySpec:
    """The spec the composed three-dispatch path runs under:
    ``backend="fused"`` is an attention-level choice the SDDMM/SpMM ops
    don't know — normalize it to ``"auto"`` for them."""
    if spec.backend == "fused":
        return dataclasses.replace(spec, backend="auto")
    return spec


def _composed_heads(qf: jnp.ndarray, kf: jnp.ndarray, vf: jnp.ndarray,
                    spec: AttnSparsitySpec, scale: float,
                    cap: Optional[float]) -> jnp.ndarray:
    """SDDMM -> block softmax -> SpMM over folded ``[G, L, d]`` heads —
    the three-dispatch reference path (and the backward route of the
    fused forward)."""
    L = qf.shape[1]
    spec = _composed_spec(spec)
    arrays, meta = attention_mask_arrays(spec.mask, L, spec.block)
    # host constants: valid = stored-and-allowed AND not a padding entry
    elem_mask = (arrays.vals > 0.5) & arrays.real_mask[:, None, None]

    def one_head(qi, ki, vi):
        scores = ops.sddmm(arrays, meta, qi, ki, backend=spec.backend,
                           bn=spec.bn, interpret=spec.interpret,
                           out_dtype=jnp.float32)
        probs = block_softmax(scores * scale, elem_mask, arrays.row_ids,
                              meta.n_block_rows, cap=cap)
        return _context_spmm(probs, arrays, meta, vi, spec)

    return jax.vmap(one_head)(qf, kf, vf)


@functools.lru_cache(maxsize=None)
def _fused_inputs(spec: AttnMaskSpec, seq_len: int, block: Tuple[int, int]):
    """Host constants for the fused kernel: the 0/1 element-mask blocks
    and the static (block-row x slot) schedule
    (``bcsr_attn.static_schedule``).  Memoized like the other mask
    pipelines; numpy, so trace-safe as closed-over constants."""
    arrays, meta = attention_mask_arrays(spec, seq_len, block)
    emask = ((arrays.vals > 0.5) &
             arrays.real_mask[:, None, None]).astype(np.float32)
    flat_idx, flat_col = bcsr_attn.static_schedule(
        arrays.row_ids, arrays.col_ids, meta.n_block_rows, meta.max_bpr)
    return emask, flat_idx, flat_col, meta


def _fused_heads(qf: jnp.ndarray, kf: jnp.ndarray, vf: jnp.ndarray,
                 spec: AttnSparsitySpec, scale: float,
                 cap: Optional[float]) -> jnp.ndarray:
    emask, flat_idx, flat_col, meta = _fused_inputs(
        spec.mask, qf.shape[1], spec.block)
    return bcsr_attn.bcsr_attn_fused(
        qf, kf, vf, emask, flat_idx, flat_col,
        n_block_rows=meta.n_block_rows, n_block_cols=meta.n_block_cols,
        block=meta.block, scale=scale, cap=cap, bn=spec.bn,
        out_dtype=jnp.float32, interpret=spec.interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attn_fused(spec: AttnSparsitySpec, scale: float, cap: Optional[float],
                qf: jnp.ndarray, kf: jnp.ndarray, vf: jnp.ndarray):
    """Fused forward, composed backward.  The statics (spec, scale, cap)
    are hashable nondiff args; the bit-for-bit forward pin is what makes
    differentiating THROUGH the composed path consistent with the fused
    primal."""
    return _fused_heads(qf, kf, vf, spec, scale, cap)


def _attn_fused_fwd(spec, scale, cap, qf, kf, vf):
    return _fused_heads(qf, kf, vf, spec, scale, cap), (qf, kf, vf)


def _attn_fused_bwd(spec, scale, cap, res, g):
    qf, kf, vf = res
    _, vjp = jax.vjp(
        lambda a, b, c: _composed_heads(a, b, c, spec, scale, cap),
        qf, kf, vf)
    return vjp(g)


_attn_fused.defvjp(_attn_fused_fwd, _attn_fused_bwd)


def resolve_attn_impl(spec: AttnSparsitySpec, seq_len: int,
                      head_dim: int) -> str:
    """``"fused"`` | ``"composed"`` — the attention-level dispatch.

    Explicit kernel backends (``xla``/``pallas``/...) and sharded score
    paths stay composed; ``backend="fused"`` forces the fused kernel;
    ``backend="auto"`` consults the ``op="attn"`` autotune family (v6
    fingerprints, disjoint from the sddmm/spmm key spaces).  Static info
    only — trace-safe."""
    if spec.shards > 0 or spec.backend not in ("auto", "fused"):
        return "composed"
    meta = attention_mask_meta(spec.mask, seq_len, spec.block)
    if meta.max_bpr <= 0:
        return "composed"   # no static schedule bound -> no fused walk
    if spec.backend == "fused":
        return "fused"
    from repro.kernels import autotune  # local import: layering
    choice = autotune.get_autotuner().pick(meta, head_dim, op="attn")
    return "fused" if choice.variant == "attn_fused" else "composed"


def block_sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           spec: AttnSparsitySpec, *,
                           scale: Optional[float] = None,
                           cap: Optional[float] = None) -> jnp.ndarray:
    """Attention with scores evaluated only on the stored mask blocks.

    q, k, v  [B, L, H, d]  (GQA callers repeat KV heads first)
    returns  [B, L, H, d] in f32 (callers cast), matching the dense-masked
             reference on the mask support.

    The per-(batch, head) instance is SDDMM -> block softmax -> SpMM —
    either as three dispatches (``vmap`` over the two custom-VJP ops with
    the mask structure closed over as constants), or, when
    ``resolve_attn_impl`` picks the fused path (``backend="auto"`` via
    the ``op="attn"`` v6 autotune family, or ``backend="fused"``), as ONE
    Pallas launch (``kernels.bcsr_attn.bcsr_attn_fused``) whose forward
    is bit-for-bit equal in f32 and whose backward reuses the composed
    dual-VJP route.

    >>> import numpy as np, jax.numpy as jnp
    >>> from repro.models import attention as A
    >>> rng = np.random.default_rng(0)
    >>> q = jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
    >>> k = jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
    >>> v = jnp.asarray(rng.standard_normal((2, 64, 2, 8)), jnp.float32)
    >>> spec = A.AttnSparsitySpec(mask=A.banded(32), block=(8, 8),
    ...                           backend="xla")
    >>> out = A.block_sparse_attention(q, k, v, spec)
    >>> out.shape
    (2, 64, 2, 8)
    >>> bool(jnp.all(jnp.isfinite(out)))
    True
    """
    B, L, H, d = q.shape
    # normalize to plain python floats so both paths scale/cap with the
    # SAME weak-typed constants (bit-for-bit pin) and the fused op's
    # nondiff args stay hashable
    scale = float(d ** -0.5 if scale is None else scale)
    cap = None if cap is None else float(cap)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, L, d).astype(jnp.float32)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, L, d).astype(jnp.float32)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, L, d).astype(jnp.float32)
    if resolve_attn_impl(spec, L, d) == "fused":
        ctx = _attn_fused(spec, scale, cap, qf, kf, vf)    # [B*H, L, d]
    else:
        ctx = _composed_heads(qf, kf, vf, spec, scale, cap)
    return ctx.reshape(B, H, L, d).transpose(0, 2, 1, 3)


# ================================================================ reporting
def attention_mask_report(spec: AttnSparsitySpec, seq_len: int,
                          head_dim: int = 0) -> dict:
    """Mask structure + kernel picks for the dry-run: nnzb, block density
    vs dense causal, the attention-level fused-vs-composed resolution,
    and the v6 ``op=attn`` / ``op=sddmm`` / ``op=spmm`` picks the spec's
    backend resolves at this sequence length.

    ``head_dim`` is the contraction width the runtime ops actually
    fingerprint with (both the SDDMM's N axis and the context SpMM's
    panel are head-dim wide per vmapped instance) — pass the model's real
    head dim or the printed picks can come from the wrong N bucket."""
    meta = attention_mask_meta(spec.mask, seq_len, spec.block)
    nbr = meta.n_block_rows
    causal_blocks = nbr * (nbr + 1) // 2
    head_n = head_dim or meta.block[1]
    cspec = _composed_spec(spec)
    sddmm_be = ops.resolve_backend(cspec.backend, cspec.bn, meta, head_n,
                                   op="sddmm")
    spmm_be = ops.resolve_backend(cspec.backend, cspec.bn, meta, head_n,
                                  op="spmm")
    from repro.kernels import autotune  # local import: layering
    attn_choice = autotune.get_autotuner().pick(meta, head_n, op="attn")
    return {
        "mask": dataclasses.asdict(spec.mask),
        "block": list(meta.block),
        "seq_len": seq_len,
        "nnzb": meta.nnzb,
        "max_bpr": meta.max_bpr,
        "block_density_vs_causal": round(meta.nnzb / max(causal_blocks, 1),
                                         4),
        "attn_impl": resolve_attn_impl(spec, seq_len, head_n),
        "attn_pick": attn_choice.variant,
        "sddmm_pick": "{}/bn{}".format(*sddmm_be),
        "spmm_pick": "{}/bn{}".format(*spmm_be),
        "shards": spec.shards,
    }
