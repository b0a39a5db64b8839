"""Transformer layer components: norms, RoPE, GQA attention (sliding window,
logit softcap, QKV bias, or block-sparse scores on a static BCSR mask —
``cfg.attn_sparsity``), MLA (DeepSeek), gated MLP (dense or block-sparse —
the paper's technique as a drop-in FFN).

Sparse-FFN layers inherit the full ``SparsitySpec`` surface through
``apply_sparse_linear`` — including ``shards="auto"`` (shard count
resolved per layer from dims alone, so scan-stacked layers keep shared
leaf shapes) and ``shard_chunks`` (the communication-overlap pipeline
depth; chunked dispatch is bit-identical to unchunked, so it is safe by
default).

Conventions:
  * params are nested dicts of jnp arrays; init fns take (cfg, key).
  * activations are [B, L, D]; caches are dicts of ring buffers written at
    ``pos % cache_len`` (works for both full and sliding-window caches).
  * attention math accumulates in f32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.core.sparse_linear import (apply_sparse_linear,
                                      init_sparse_linear,
                                      merge_sparse_metas,
                                      sparse_linear_meta)
from repro.models import unroll as U
from repro.obs import jaxmon

# chunk size for q-blocked (flash-style, O(L*chunk) memory) attention
Q_CHUNK = 1024
NEG_INF = -2.0e38


# ------------------------------------------------------------------ basics
def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def _rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """DeepSeek's ``yarn_get_mscale``: the attention temperature YaRN
    applies at context-extension ``scale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(yarn, dim: int, theta: float):
    """The rotary dims ``[low, high]`` between which YaRN blends from
    extrapolated to interpolated frequencies (DeepSeek's
    ``yarn_find_correction_range``)."""
    def dim_of(rotations):
        return dim * math.log(yarn.original_max_position_embeddings /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))
    low = math.floor(dim_of(yarn.beta_fast))
    high = math.ceil(dim_of(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_freqs(dim: int, theta: float, yarn) -> np.ndarray:
    """[dim/2] float32 inverse frequencies of DeepSeek's
    ``DeepseekV2YarnRotaryEmbedding``: ``freq / factor`` below the
    correction range, ``freq`` above it, a linear ramp between."""
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    low, high = yarn_correction_range(yarn, dim, theta)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    extra = 1.0 - ramp
    return (freq / yarn.factor * (1 - extra) + freq * extra).astype(
        np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None):
    """x [..., L, H, dh]; positions [..., L] int32 (broadcastable).
    ``yarn`` (a ``configs.base.YarnRope``) switches to YaRN frequencies,
    with cos/sin scaled by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)`` as DeepSeek does (1 when the two are equal)."""
    dh = x.shape[-1]
    if yarn is None:
        freqs = _rope_freqs(dh, theta)                   # [dh/2]
        mag = 1.0
    else:
        freqs = jnp.asarray(yarn_freqs(dh, theta, yarn))
        mag = yarn_mscale(yarn.factor, yarn.mscale) / \
            yarn_mscale(yarn.factor, yarn.mscale_all_dim)
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., L, dh/2]
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    if mag != 1.0:
        sin, cos = sin * mag, cos * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _dense(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ================================================================= attention
def init_attention(cfg, key, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": _init(ks[0], (d, h * hd), s, dtype),
        "wk": _init(ks[1], (d, kv * hd), s, dtype),
        "wv": _init(ks[2], (d, kv * hd), s, dtype),
        "wo": _init(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _mask_bias(q_pos, k_pos, window):
    """[..., Lq, Sk] additive mask: causal + optional sliding window +
    validity (k_pos >= 0)."""
    ok = (k_pos[..., None, :] <= q_pos[..., :, None]) & \
         (k_pos[..., None, :] >= 0)
    if window is not None:
        ok &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def _sdpa(q, k, v, bias, cap, scale):
    """q [B,Lq,H,dh] k [B,S,KV,dh] v [B,S,KV,dv] bias [B,Lq,S]
    -> [B,Lq,H,dv] (dv may differ from dh, e.g. MLA)."""
    B, Lq, H, dh = q.shape
    KV = k.shape[2]
    dv = v.shape[-1]
    rep = H // KV
    qg = q.reshape(B, Lq, KV, rep, dh)
    scores = jnp.einsum("blgrd,bsgd->bgrls", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cap)
    scores = scores + bias[:, None, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgrls,bsgd->blgrd", probs, v.astype(jnp.float32))
    return ctx.reshape(B, Lq, H, dv)


def _sparse_mask(cfg, window):
    """Effective mask spec of the block-sparse attention path: the config's
    static pattern, intersected with the layer's sliding window when one is
    set (gemma-style local halves keep their window under sparse scores)."""
    mask = cfg.attn_sparsity.mask
    if window is not None:
        mask = dataclasses.replace(mask, window_cap=int(window))
    return mask


def _decode_pages(cfg, window, cache_len):
    """Static paged-decode resolution for one attention layer: the mask
    page table (host constants) when the paged KV path applies, else None
    (dense-bias decode).  ``AttnSparsitySpec.paged_decode`` gates it:
    "auto" requires a strict page saving (``max_bpr < n_pages``), "force"
    only structural feasibility, "off" disables.  Trace-safe — depends on
    static config only."""
    sparse = getattr(cfg, "attn_sparsity", None)
    if sparse is None:
        return None
    mode = getattr(sparse, "paged_decode", "auto")
    if mode == "off":
        return None
    w = sparse.block[1]
    if cache_len % w != 0:          # pages must tile the KV ring exactly
        return None
    from repro.models import attention as A
    pages, live, meta = A.decode_page_table(
        _sparse_mask(cfg, window), cache_len, sparse.block)
    if meta.max_bpr <= 0:
        return None
    if mode != "force" and meta.max_bpr >= cache_len // w:
        return None                 # no page saving: keep the dense bias
    return pages, live


@jaxmon.monitor(name="models.paged_decode")
def _paged_decode(cfg, q, kc, vc, pos, window, cap, scale, *,
                  pages, live):
    """One-token decode attention reading KV through the mask page table
    (``attention.decode_page_table``) instead of biasing the dense cache.

    Only the ``max_bpr`` pages of block-row ``pos // block_h`` are
    gathered; softmax combines them as a SEQUENTIAL per-page fold in
    ascending key order (exact running max, then denominator and context
    accumulated page by page).  A page absent from the table contributes
    exactly 0.0 to the denominator and context and never attains the max,
    and inserting exact zeros into a sequential add chain is a bitwise
    no-op — so this path is bit-for-bit equal in f32 to the same fold
    over the FULL page table (the dense-bias reference arm pinned per
    mask family in ``tests/test_serving.py``).

    Positions are taken as ``page * w + offset``: the paged path assumes
    the ring has not wrapped (``pos < cache_len``), which the serving
    scheduler enforces at admission (``len(prompt) + max_new_tokens <=
    cache_len``)."""
    from repro.models import attention as A
    spec = _sparse_mask(cfg, window)
    B, _, H, dh = q.shape
    Sc, KV = kc.shape[1], kc.shape[2]
    h, w = cfg.attn_sparsity.block
    n_pages = Sc // w
    nbr = pages.shape[0]
    row = jnp.clip(pos // h, 0, nbr - 1)
    cols = jnp.asarray(pages)[row]                        # [P]
    alive = jnp.asarray(live)[row]                        # [P]
    P = int(cols.shape[0])
    kp = kc.reshape(B, n_pages, w, KV, dh)[:, cols]       # [B,P,w,KV,dh]
    vp = vc.reshape(B, n_pages, w, KV, dh)[:, cols]
    k_pos = (cols[:, None] * w +
             jnp.arange(w, dtype=jnp.int32)[None]).reshape(-1)   # [P*w]
    qpos = jnp.reshape(pos, (1,))
    bias = (_mask_bias(qpos, k_pos, window) +
            A.decode_mask_bias(spec, qpos, k_pos))[0].reshape(P, w)
    bias = jnp.where(alive[:, None], bias, NEG_INF)
    rep = H // KV
    qg = q.reshape(B, KV, rep, dh).astype(jnp.float32)    # L == 1 squeezed
    scores = jnp.einsum("bgrd,bpwgd->bgrpw", qg,
                        kp.astype(jnp.float32)) * scale
    scores = softcap(scores, cap)
    biased = scores + bias[None, None, None]              # [B,g,r,P,w]
    m = jnp.max(biased, axis=(3, 4))                      # exact any order
    z = jnp.exp(biased - m[..., None, None])
    page_sum = z.sum(axis=4)                              # [B,g,r,P]
    partial = jnp.einsum("bgrpw,bpwgd->bgrpd", z,
                         vp.astype(jnp.float32))          # [B,g,r,P,dh]
    denom = jnp.zeros(m.shape, jnp.float32)
    ctx = jnp.zeros(m.shape + (dh,), jnp.float32)
    for p in range(P):      # static P: unrolled sequential add chains
        denom = denom + page_sum[..., p]
        ctx = ctx + partial[..., p, :]
    ctx = ctx / denom[..., None]
    return ctx.reshape(B, 1, H, dh)


def _sparse_attention(cfg, q, k, v, window, cap, scale):
    """Full-sequence attention through ``models.attention``: SDDMM scores
    on the static BCSR mask, masked block softmax, SpMM context.  Replaces
    ``_causal_attention`` when ``cfg.attn_sparsity`` is set."""
    from repro.models import attention as A
    spec = dataclasses.replace(cfg.attn_sparsity,
                               mask=_sparse_mask(cfg, window))
    rep = q.shape[2] // k.shape[2]
    if rep > 1:                     # GQA: expand KV heads for per-head ops
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return A.block_sparse_attention(q, k, v, spec, scale=scale, cap=cap)


def attention(cfg, p, x, *, window=None, cache=None, pos=None,
              rope_theta=None):
    """Returns (y, new_cache).  Modes:
      train:    cache None, pos None — full causal self-attention.
      prefill:  cache dict (zeroed, len >= L), pos = 0 — causal + cache write.
      decode:   cache dict, L == 1, pos = current position (int32 scalar).

    With ``cfg.attn_sparsity`` set, train/prefill score the static BCSR
    mask through the SDDMM/SpMM pair (``models.attention``) and decode
    applies the SAME mask spec as a positional bias — served tokens stay
    consistent with how the model trains.
    """
    B, L, D = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    theta = rope_theta or cfg.rope_theta
    q = _dense(x, p["wq"], p.get("bq")).reshape(B, L, h, dh)
    k = _dense(x, p["wk"], p.get("bk")).reshape(B, L, kv, dh)
    v = _dense(x, p["wv"], p.get("bv")).reshape(B, L, kv, dh)

    if cache is None or pos is None:        # training: positions 0..L-1
        positions = jnp.arange(L, dtype=jnp.int32)[None, :]
    else:
        positions = (pos + jnp.arange(L, dtype=jnp.int32))[None, :]
    q = apply_rope(q, jnp.broadcast_to(positions, (B, L)), theta)
    k = apply_rope(k, jnp.broadcast_to(positions, (B, L)), theta)

    scale = dh ** -0.5
    cap = cfg.attn_logit_softcap

    sparse = getattr(cfg, "attn_sparsity", None)
    if cache is None:
        if sparse is not None:
            ctx = _sparse_attention(cfg, q, k, v, window, cap, scale)
        else:
            ctx = _causal_attention(q, k, v, window, cap, scale)
        new_cache = None
    elif L > 1:                              # prefill into empty cache
        Sc = cache["k"].shape[1]
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k[:, -Sc:].astype(cache["k"].dtype), (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v[:, -Sc:].astype(cache["v"].dtype), (0, 0, 0, 0))
        new_cache = {"k": kc, "v": vc}
        if sparse is not None:
            ctx = _sparse_attention(cfg, q, k, v, window, cap, scale)
        else:
            ctx = _causal_attention(q, k, v, window, cap, scale)
    else:                                    # decode one token
        Sc = cache["k"].shape[1]
        slot = pos % Sc
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": kc, "v": vc}
        table = _decode_pages(cfg, window, Sc)
        if table is not None:
            # paged KV: gather only the mask row's pages (serve.paged_kv)
            ctx = _paged_decode(cfg, q, kc, vc, pos, window, cap, scale,
                                pages=table[0], live=table[1])
        else:
            j = jnp.arange(Sc, dtype=jnp.int32)
            k_pos = pos - ((pos - j) % Sc)   # ring-buffer slot positions
            bias = _mask_bias(jnp.reshape(pos, (1,)), k_pos,
                              window)        # [1, Sc]
            if sparse is not None:
                # the decode twin of the block-sparse score mask
                from repro.models import attention as A
                bias = bias + A.decode_mask_bias(
                    _sparse_mask(cfg, window), jnp.reshape(pos, (1,)),
                    k_pos)
            bias = jnp.broadcast_to(bias[None], (B, 1, Sc))
            ctx = _sdpa(q, kc, vc, bias, cap, scale)

    y = _dense(ctx.reshape(B, L, h * dh).astype(x.dtype), p["wo"])
    return y, new_cache


def _causal_attention(q, k, v, window, cap, scale):
    """Full causal attention, q-chunked above Q_CHUNK (O(L*chunk) scores
    memory — the flash-attention analogue for the 32k prefill cells)."""
    B, L, H, dh = q.shape
    pos = jnp.arange(L, dtype=jnp.int32)
    if L <= Q_CHUNK:
        bias = _mask_bias(pos, pos, window)[None]
        return _sdpa(q, k, v, jnp.broadcast_to(bias, (B, L, L)), cap, scale)

    n_chunks = L // Q_CHUNK
    assert L % Q_CHUNK == 0, (L, Q_CHUNK)

    def chunk_fn(carry, qi):
        q_chunk, q_pos = qi                     # [B, C, H, dh], [C]
        bias = _mask_bias(q_pos, pos, window)[None]
        ctx = _sdpa(q_chunk, k, v, jnp.broadcast_to(bias, (B, Q_CHUNK, L)),
                    cap, scale)
        return carry, ctx

    q_chunks = q.reshape(B, n_chunks, Q_CHUNK, H, dh).transpose(1, 0, 2, 3, 4)
    pos_chunks = pos.reshape(n_chunks, Q_CHUNK)
    _, ctxs = U.scan(chunk_fn, None, (q_chunks, pos_chunks))
    dv = ctxs.shape[-1]                      # may differ from dh (MLA)
    return ctxs.transpose(1, 0, 2, 3, 4).reshape(B, L, H, dv)


def init_attn_cache(cfg, batch, cache_len, dtype, window=None):
    Sc = min(cache_len, window) if window else cache_len
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, Sc, kv, dh), dtype),
            "v": jnp.zeros((batch, Sc, kv, dh), dtype)}


# ======================================================================= MLA
def init_mla(cfg, key, dtype):
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, vd, r = (cfg.nope_head_dim, cfg.rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    p = {}
    q_dim = h * (nope + rope)
    if cfg.q_lora_rank:
        p["wq_a"] = _init(ks[0], (d, cfg.q_lora_rank), s, dtype)
        p["q_norm"] = jnp.zeros((cfg.q_lora_rank,), jnp.float32)
        p["wq_b"] = _init(ks[1], (cfg.q_lora_rank, q_dim),
                          cfg.q_lora_rank ** -0.5, dtype)
    else:
        p["wq"] = _init(ks[0], (d, q_dim), s, dtype)
    p["wkv_a"] = _init(ks[2], (d, r + rope), s, dtype)
    p["kv_norm"] = jnp.zeros((r,), jnp.float32)
    p["wkv_b"] = _init(ks[3], (r, h * (nope + vd)), r ** -0.5, dtype)
    p["wo"] = _init(ks[4], (h * vd, d), (h * vd) ** -0.5, dtype)
    return p


def mla_softmax_scale(cfg) -> float:
    """``(nope + rope) ** -0.5``, times ``mscale(factor, mscale_all_dim)
    ** 2`` under YaRN (DeepSeek's ``softmax_scale``)."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    yarn = cfg.rope_scaling
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def mla_attention(cfg, p, x, *, cache=None, pos=None):
    """Multi-head Latent Attention.  Cache holds the compressed latent
    (c_kv, k_rope) only — decode uses the absorbed-matrix form."""
    with jax.named_scope("model.mla"):
        return _mla_attention(cfg, p, x, cache=cache, pos=pos)


def _mla_attention(cfg, p, x, *, cache=None, pos=None):
    B, L, D = x.shape
    h = cfg.n_heads
    nope, rope, vd, r = (cfg.nope_head_dim, cfg.rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    theta = cfg.rope_theta

    if cfg.q_lora_rank:
        q = _dense(rms_norm(_dense(x, p["wq_a"]), p["q_norm"]), p["wq_b"])
    else:
        q = _dense(x, p["wq"])
    q = q.reshape(B, L, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = _dense(x, p["wkv_a"])                         # [B, L, r + rope]
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"])
    k_rope = kv_a[..., r:].reshape(B, L, 1, rope)

    if cache is None or pos is None:
        positions = jnp.arange(L, dtype=jnp.int32)[None, :]
    else:
        positions = (pos + jnp.arange(L, dtype=jnp.int32))[None, :]
    positions = jnp.broadcast_to(positions, (B, L))
    q_rope = apply_rope(q_rope, positions, theta, cfg.rope_scaling)
    k_rope = apply_rope(k_rope, positions, theta, cfg.rope_scaling)

    scale = mla_softmax_scale(cfg)
    w_kv_b = p["wkv_b"].reshape(r, h, nope + vd)
    w_uk, w_uv = w_kv_b[..., :nope], w_kv_b[..., nope:]

    if cache is not None and L == 1:
        # ---- absorbed decode: score against the latent cache directly
        Sc = cache["ckv"].shape[1]
        slot = pos % Sc
        ckv_c = jax.lax.dynamic_update_slice(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, slot, 0))
        krope_c = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope[:, :, 0].astype(cache["krope"].dtype),
            (0, slot, 0))
        new_cache = {"ckv": ckv_c, "krope": krope_c}
        q_lat = jnp.einsum("blhn,rhn->blhr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))       # [B,1,h,r]
        s_lat = jnp.einsum("blhr,bsr->bhls", q_lat,
                           ckv_c.astype(jnp.float32))
        s_rope = jnp.einsum("blhe,bse->bhls", q_rope.astype(jnp.float32),
                            krope_c.astype(jnp.float32))
        scores = (s_lat + s_rope) * scale
        j = jnp.arange(Sc, dtype=jnp.int32)
        k_pos = pos - ((pos - j) % Sc)
        bias = _mask_bias(jnp.reshape(pos, (1,)), k_pos, None)   # [1, Sc]
        probs = jax.nn.softmax(scores + bias[None, None], axis=-1)
        ctx_lat = jnp.einsum("bhls,bsr->blhr", probs,
                             ckv_c.astype(jnp.float32))
        ctx = jnp.einsum("blhr,rhv->blhv", ctx_lat, w_uv.astype(jnp.float32))
    else:
        # ---- train/prefill: materialize per-head K, V — constrained to
        # heads-over-model so sequence gathers move the 576-dim latent and
        # 1/16 head slices, not the full 128-head expansion (§Perf B2)
        from repro.launch.constrain import BATCH, MODEL, constrain
        k_nope = jnp.einsum("blr,rhn->blhn", c_kv, w_uk.astype(c_kv.dtype))
        v = jnp.einsum("blr,rhv->blhv", c_kv, w_uv.astype(c_kv.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, L, h, rope))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        qq = constrain(qq, BATCH, None, MODEL)
        k = constrain(k, BATCH, None, MODEL)
        v = constrain(v, BATCH, None, MODEL)
        ctx = _causal_attention(qq, k, v, None, None, scale)  # [B,L,h,vd]
        ctx = _checkpoint_name(ctx, "attn_ctx")
        if cache is not None:               # prefill: write latent cache
            Sc = cache["ckv"].shape[1]
            ckv_c = jax.lax.dynamic_update_slice(
                cache["ckv"], c_kv[:, -Sc:].astype(cache["ckv"].dtype),
                (0, 0, 0))
            krope_c = jax.lax.dynamic_update_slice(
                cache["krope"], k_rope[:, -Sc:, 0].astype(
                    cache["krope"].dtype), (0, 0, 0))
            new_cache = {"ckv": ckv_c, "krope": krope_c}
        else:
            new_cache = None

    y = _dense(ctx.reshape(B, L, h * vd).astype(x.dtype), p["wo"])
    return y, new_cache


def init_mla_cache(cfg, batch, cache_len, dtype):
    return {"ckv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, cache_len, cfg.rope_head_dim), dtype)}


# ======================================================================= MLP
# python-int seed of the structural sparse pattern for one init_mlp call —
# THE single derivation site: init_mlp (builds params) and mlp_sparse_metas
# (re-derives static metas at apply time) must agree or the apply path
# would dispatch on stats of a structure the params don't have.
MLP_SEED_BASE = 7919


def mlp_seed(seed_hint: int) -> int:
    """Pattern seed of ``init_mlp(..., seed_hint=...)``'s gate weight (up
    uses ``+1``, down ``+2``)."""
    return MLP_SEED_BASE * (seed_hint + 1)


def init_mlp(cfg, key, dtype, d_ff=None, seed_hint: int = 0):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.ffn_sparsity is not None:
        # sparse patterns are STRUCTURAL (host-side numpy): seeded by a
        # python int per layer, not the traced jax key — this keeps
        # init_params eval_shape-able for the dry-run.  The spec's
        # ``reorder`` scheme is applied here too (block-row granularity,
        # so every layer keeps the same nnzb and the stack still scans);
        # apply_sparse_linear sees it via the row_perm/inv_perm leaves and
        # the static metas mlp() re-derives, and un-permutes outputs
        # transparently.
        seed = mlp_seed(seed_hint)
        gate, _ = init_sparse_linear(seed, d, f, cfg.ffn_sparsity, dtype)
        up, _ = init_sparse_linear(seed + 1, d, f, cfg.ffn_sparsity, dtype)
        down, _ = init_sparse_linear(seed + 2, f, d, cfg.ffn_sparsity, dtype)
        return {"gate": gate, "up": up, "down": down}
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init(ks[0], (d, f), d ** -0.5, dtype),
        "w_up": _init(ks[1], (d, f), d ** -0.5, dtype),
        "w_down": _init(ks[2], (f, d), f ** -0.5, dtype),
    }


@functools.lru_cache(maxsize=None)
def mlp_sparse_metas(spec, d: int, f: int, seed_hints: tuple):
    """TRUE structure metas of a (possibly scan-stacked) sparse MLP.

    ``seed_hints`` are the ``init_mlp`` seed hints of every layer sharing
    the traced body (one hint for an unstacked block, ``range(n_layers)``
    for the transformer's scanned stack).  Per-layer metas are re-derived
    from the deterministic pattern seeds (``sparse_linear_meta`` — real
    ``max_bpr``/padding/skew, per-shard ``ShardedMeta`` stats) and merged
    conservatively (``merge_sparse_metas``: stats take the stack max, so
    one static meta is correct for every layer the scan applies).  Gate
    and up share dims ``d -> f`` and both fold into ``meta_in``; down is
    ``f -> d`` (``meta_out``).  Returns ``(meta_in, meta_out)`` —
    hashable STATIC aux data, never pytree leaves."""
    metas_in, metas_out = [], []
    for hint in seed_hints:
        seed = mlp_seed(hint)
        metas_in.append(sparse_linear_meta(seed, d, f, spec))        # gate
        metas_in.append(sparse_linear_meta(seed + 1, d, f, spec))    # up
        metas_out.append(sparse_linear_meta(seed + 2, f, d, spec))   # down
    return merge_sparse_metas(metas_in), merge_sparse_metas(metas_out)


def mlp(cfg, p, x, d_ff=None, seed_hints=(0,)):
    """Gated MLP (dense, or block-sparse when ``cfg.ffn_sparsity`` is set
    AND ``p`` holds sparse params).

    The sparse path dispatches on the static metas of the structures
    ``init_mlp`` actually built: pass the same ``seed_hints`` the params
    were initialized with (every hint sharing this traced body — the
    layer-scan callers in ``models.transformer`` pass the whole stack's
    hints).  That is what gives the model path heterogeneous per-shard
    autotune picks and real ``max_bpr`` bounds instead of the dims-only
    collapse."""
    act = jax.nn.silu if cfg.mlp_act == "silu" else \
        functools.partial(jax.nn.gelu, approximate=True)
    if cfg.ffn_sparsity is not None and "gate" in p:
        d, f = cfg.d_model, d_ff or cfg.d_ff
        meta_in, meta_out = mlp_sparse_metas(cfg.ffn_sparsity, d, f,
                                             tuple(seed_hints))
        g = apply_sparse_linear(p["gate"], meta_in, x, cfg.ffn_sparsity)
        u = apply_sparse_linear(p["up"], meta_in, x, cfg.ffn_sparsity)
        return apply_sparse_linear(p["down"], meta_out, act(g) * u,
                                   cfg.ffn_sparsity)
    return _dense(act(_dense(x, p["w_gate"])) * _dense(x, p["w_up"]),
                  p["w_down"])
