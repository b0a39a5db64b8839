"""Model assembly: embeddings, scanned layer stacks (homogeneous segments
keep the HLO small at 512 devices), caches, and the train/prefill/decode
entry points.

Layouts:
  attn_mlp    — standard decoder (dense archs, pixtral/musicgen backbones,
                smat_ffn with block-sparse FFN)
  gemma_pair  — (local SWA + global) pair scanned n_layers/2 times, softcaps
  mla_moe     — DeepSeek MLA attention + shared/routed MoE FFN, after
                ``first_k_dense_replace`` leading MLA + dense-FFN layers
                (their own stack, ``params["dense_blocks"]``)
  ssd         — Mamba2 (attention-free)
  zamba       — units of (unit_len x mamba2) + ONE shared attention block
                (params reused across units) + mamba tail
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import unroll as U


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ================================================================ block defs
def _init_block(cfg: ModelConfig, key, dtype, seed_hint: int = 0):
    """One repeating unit of the layer stack."""
    if cfg.layout == "attn_mlp":
        k1, k2 = jax.random.split(key)
        return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": L.init_attention(cfg, k1, dtype),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "mlp": L.init_mlp(cfg, k2, dtype,
                                  seed_hint=seed_hint)}
    if cfg.layout == "gemma_pair":
        ks = jax.random.split(key, 4)
        def half(ka, kb):
            return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                    "ln1_post": jnp.zeros((cfg.d_model,), jnp.float32),
                    "attn": L.init_attention(cfg, ka, dtype),
                    "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                    "ln2_post": jnp.zeros((cfg.d_model,), jnp.float32),
                    "mlp": L.init_mlp(cfg, kb, dtype)}
        return {"local": half(ks[0], ks[1]), "global": half(ks[2], ks[3])}
    if cfg.layout == "mla_moe":
        k1, k2 = jax.random.split(key)
        return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "mla": L.init_mla(cfg, k1, dtype),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "moe": M.init_moe(cfg, k2, dtype)}
    if cfg.layout == "mla_dense":
        k1, k2 = jax.random.split(key)
        return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "mla": L.init_mla(cfg, k1, dtype),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "mlp": L.init_mlp(cfg, k2, dtype)}
    if cfg.layout == "ssd":
        return {"ln": jnp.zeros((cfg.d_model,), jnp.float32),
                "ssd": S.init_ssd(cfg, key, dtype)}
    raise ValueError(cfg.layout)


def _mlp_seed_hints(cfg: ModelConfig):
    """``init_mlp`` seed hints of every sparse-FFN layer sharing the scanned
    block body — the static aux data ``L.mlp`` re-derives its structure
    metas from.  ``attn_mlp`` stacks init with ``seed_hint=i`` (see
    ``init_params``); every other layout inits its mlps with the default
    hint 0."""
    if cfg.layout == "attn_mlp":
        return tuple(range(_n_repeats(cfg)))
    return (0,)


def _apply_block(cfg: ModelConfig, p, x, cache, pos, capture: bool = False):
    """Returns (x, new_cache, aux, stats); ``stats`` is ``{}`` except for
    a ``bcsr`` MoE block (``moe.moe_layer``, which ``capture`` is passed
    to)."""
    from repro.launch.constrain import BATCH, MODEL, constrain
    if x.shape[1] > 1:
        # sequence-parallel carry (Megatron-SP): norms/FFN run L-sharded;
        # GSPMD gathers L only where attention needs the full sequence.
        x = constrain(x, BATCH, MODEL)
    aux = jnp.zeros((), jnp.float32)
    if cfg.layout == "attn_mlp":
        a, c = L.attention(cfg, p["attn"], L.rms_norm(x, p["ln1"]),
                           window=cfg.sliding_window, cache=cache, pos=pos)
        x = x + a
        x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"]),
                      seed_hints=_mlp_seed_hints(cfg))
        return x, c, aux, {}
    if cfg.layout == "gemma_pair":
        caches = cache or {"local": None, "global": None}
        new_c = {}
        for kind, window in (("local", cfg.sliding_window), ("global", None)):
            h = p[kind]
            a, c = L.attention(cfg, h["attn"], L.rms_norm(x, h["ln1"]),
                               window=window, cache=caches[kind], pos=pos)
            x = x + L.rms_norm(a, h["ln1_post"])
            m = L.mlp(cfg, h["mlp"], L.rms_norm(x, h["ln2"]),
                      seed_hints=_mlp_seed_hints(cfg))
            x = x + L.rms_norm(m, h["ln2_post"])
            new_c[kind] = c
        return x, (new_c if cache is not None else None), aux, {}
    if cfg.layout in ("mla_moe", "mla_dense"):
        a, c = L.mla_attention(cfg, p["mla"], L.rms_norm(x, p["ln1"]),
                               cache=cache, pos=pos)
        x = x + a
        if cfg.layout == "mla_dense":
            with jax.named_scope("model.dense_ffn"):
                x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"]))
            return x, c, aux, {}
        y, aux, stats = M.moe_layer(cfg, p["moe"], L.rms_norm(x, p["ln2"]),
                                    dispatch=cfg.moe_dispatch,
                                    capture=capture)
        return x + y, c, aux, stats
    if cfg.layout == "ssd":
        y, c = S.ssd_block(cfg, p["ssd"], L.rms_norm(x, p["ln"]),
                           cache=cache, pos=pos)
        return x + y, c, aux, {}
    raise ValueError(cfg.layout)


def _block_cache(cfg: ModelConfig, batch, cache_len, dtype):
    if cfg.layout == "attn_mlp":
        return L.init_attn_cache(cfg, batch, cache_len, dtype,
                                 window=cfg.sliding_window)
    if cfg.layout == "gemma_pair":
        return {"local": L.init_attn_cache(cfg, batch, cache_len, dtype,
                                           window=cfg.sliding_window),
                "global": L.init_attn_cache(cfg, batch, cache_len, dtype)}
    if cfg.layout in ("mla_moe", "mla_dense"):
        return L.init_mla_cache(cfg, batch, cache_len, dtype)
    if cfg.layout == "ssd":
        return S.init_ssd_cache(cfg, batch, dtype)
    raise ValueError(cfg.layout)


def _n_repeats(cfg: ModelConfig) -> int:
    if cfg.layout == "gemma_pair":
        return cfg.n_layers // 2
    if cfg.layout == "mla_moe":
        return cfg.n_layers - cfg.first_k_dense_replace
    if cfg.layout == "mla_dense":
        return cfg.first_k_dense_replace
    return cfg.n_layers


@functools.lru_cache(maxsize=None)
def _dense_view(cfg: ModelConfig) -> ModelConfig:
    """The leading dense layers of an ``mla_moe`` config as a layout of
    their own (MLA + the ``d_ff`` gated MLP)."""
    import dataclasses
    return dataclasses.replace(cfg, layout="mla_dense")


def _stacks(cfg: ModelConfig):
    """``(params key, view cfg)`` of each scanned stack, in order."""
    if cfg.layout == "mla_moe" and cfg.first_k_dense_replace:
        return (("dense_blocks", _dense_view(cfg)), ("blocks", cfg))
    return (("blocks", cfg),)


# ============================================================= params (full)
def init_params(cfg: ModelConfig, seed: int = 0, key=None) -> Dict[str, Any]:
    """Random parameters from ``seed``, or from the PRNG ``key`` when given
    (a traced key lets one jitted init serve every seed)."""
    dtype = _dtype(cfg)
    if key is None:
        key = jax.random.PRNGKey(seed)
    d = cfg.d_model
    k_embed, k_head, k_blocks, k_shared = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if cfg.input_mode == "codebooks":
        params["embed"] = (jax.random.normal(
            k_embed, (cfg.n_codebooks, cfg.vocab_size, d)) * 0.02
        ).astype(dtype)
        params["lm_head"] = (jax.random.normal(
            k_head, (cfg.n_codebooks, d, cfg.vocab_size)) * d ** -0.5
        ).astype(dtype)
    else:
        params["embed"] = (jax.random.normal(
            k_embed, (cfg.vocab_size, d)) * 0.02).astype(dtype)
        params["lm_head"] = (jax.random.normal(
            k_head, (d, cfg.vocab_size)) * d ** -0.5).astype(dtype)

    if cfg.layout == "zamba":
        n_mamba = cfg.hybrid_unit_len * cfg.hybrid_n_units
        mamba_cfgs = jax.random.split(k_blocks, n_mamba + cfg.hybrid_tail)
        ssd_cfg = cfg
        unit = []
        for u in range(cfg.hybrid_n_units):
            sub = [{"ln": jnp.zeros((d,), jnp.float32),
                    "ssd": S.init_ssd(ssd_cfg, mamba_cfgs[u * cfg.hybrid_unit_len + i], dtype)}
                   for i in range(cfg.hybrid_unit_len)]
            unit.append(_stack(sub))
        params["units"] = _stack(unit)             # [n_units, unit_len, ...]
        tail = [{"ln": jnp.zeros((d,), jnp.float32),
                 "ssd": S.init_ssd(ssd_cfg, mamba_cfgs[n_mamba + i], dtype)}
                for i in range(cfg.hybrid_tail)]
        params["tail"] = _stack(tail)
        k1, k2 = jax.random.split(k_shared)
        params["shared_attn"] = {
            "ln1": jnp.zeros((d,), jnp.float32),
            "attn": L.init_attention(cfg, k1, dtype),
            "ln2": jnp.zeros((d,), jnp.float32),
            "mlp": L.init_mlp(cfg, k2, dtype)}
    else:
        stacks = _stacks(cfg)
        keys = list(jax.random.split(
            k_blocks, sum(_n_repeats(view) for _, view in stacks)))
        for name, view in stacks:
            n = _n_repeats(view)
            params[name] = _stack(
                [_init_block(view, keys.pop(0), dtype, seed_hint=i)
                 for i in range(n)])
    return params


def param_specs(cfg: ModelConfig):
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    return jax.eval_shape(functools.partial(init_params, cfg))


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Stacked decode caches for the whole network."""
    dtype = _dtype(cfg)
    if cfg.layout == "zamba":
        unit_c = [_stack([S.init_ssd_cache(cfg, batch, dtype)
                          for _ in range(cfg.hybrid_unit_len)])
                  for _ in range(cfg.hybrid_n_units)]
        return {
            "units_ssd": _stack(unit_c),
            "units_attn": _stack([L.init_attn_cache(cfg, batch, cache_len,
                                                    dtype)
                                  for _ in range(cfg.hybrid_n_units)]),
            "tail_ssd": _stack([S.init_ssd_cache(cfg, batch, dtype)
                                for _ in range(cfg.hybrid_tail)]),
        }
    # one zeroed buffer per leaf: stacking per-layer zeros would hold the
    # whole cache twice at its peak (the full-width KV cache is GBs)
    def stacked(view):
        n = _n_repeats(view)
        one = jax.eval_shape(
            functools.partial(_block_cache, view, batch, cache_len, dtype))
        return jax.tree.map(lambda s: jnp.zeros((n,) + s.shape, s.dtype),
                            one)
    stacks = _stacks(cfg)
    if len(stacks) == 1:
        return stacked(cfg)
    return {name: stacked(view) for name, view in stacks}


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, cache_len))


# ================================================================== forward
def _embed(cfg: ModelConfig, params, batch_in) -> jnp.ndarray:
    tokens = batch_in["tokens"]
    if cfg.input_mode == "codebooks":
        # tokens [B, L, n_cb] — sum the codebook embeddings
        x = sum(params["embed"][c][tokens[..., c]]
                for c in range(cfg.n_codebooks))
    else:
        x = params["embed"][tokens]                         # [B, L, D]
    if cfg.layout == "gemma_pair":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.input_mode == "tokens+patches" and "patch_embeds" in batch_in:
        pe = batch_in["patch_embeds"].astype(x.dtype)       # [B, P, D]
        x = jnp.concatenate([pe, x], axis=1)
    return x


def _head(cfg: ModelConfig, params, x) -> jnp.ndarray:
    with jax.named_scope("model.head"):
        x = L.rms_norm(x, params["final_norm"])
        if cfg.input_mode == "codebooks":
            logits = jnp.einsum("bld,cdv->blcv", x, params["lm_head"])
        else:
            logits = jnp.einsum("bld,dv->blv", x, params["lm_head"])
        return L.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)


def _scan_stack(cfg, stacked, x, caches, pos, remat: str,
                capture: bool = False):
    """Scan blocks over the leading stack axis; caches ride as xs/ys."""
    fn = functools.partial(_apply_block, cfg, capture=capture)
    if remat == "full":
        fn = jax.checkpoint(fn)
    elif remat == "dots":
        fn = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat == "names":
        # save exactly the tensors whose recomputation is collective-heavy
        # (attention context; gathered expert outputs) — §Perf B3
        fn = jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_ctx", "moe_eout"))

    if caches is None:
        def body(carry, p):
            x, aux = carry
            x2, _, a, st = fn(p, x, None, None)
            return (x2, aux + a), st
        (x, aux), stats = U.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 stacked)
        return x, None, aux, stats

    def body(carry, xs):
        x, aux = carry
        p, c = xs
        x2, c2, a, st = fn(p, x, c, pos)
        return (x2, aux + a), (c2, st)
    (x, aux), (new_caches, stats) = U.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stacked, caches))
    return x, new_caches, aux, stats


def _forward_stacks(cfg, params, x, caches, pos, remat, capture=False):
    """Every scanned stack in turn (``_stacks``).  Returns ``(x, caches,
    aux, stats)``, ``stats`` the per-layer ``moe_layer`` stats stacked
    over the layers (``{}`` without a ``bcsr`` MoE)."""
    stacks = _stacks(cfg)
    if len(stacks) == 1:
        return _scan_stack(cfg, params["blocks"], x, caches, pos, remat,
                           capture)
    aux, stats, new_caches = jnp.zeros((), jnp.float32), {}, {}
    for name, view in stacks:
        c = None if caches is None else caches[name]
        x, new_caches[name], a, st = _scan_stack(view, params[name], x, c,
                                                 pos, remat, capture)
        aux, stats = aux + a, {**stats, **st}
    return x, (None if caches is None else new_caches), aux, stats


def _zamba_forward(cfg, params, x, caches, pos, remat):
    shared = params["shared_attn"]
    aux0 = jnp.zeros((), jnp.float32)

    def unit_body(carry, xs):
        x, aux = carry
        if caches is None:
            p_unit = xs
            c_ssd = None
            c_attn = None
        else:
            p_unit, c_ssd, c_attn = xs

        # inner scan over the unit's mamba layers
        def inner(carry2, xs2):
            x2 = carry2
            if c_ssd is None:
                p2 = xs2
                y, _, _, _ = _apply_block(_ssd_view(cfg), p2, x2, None,
                                          None)
                return y, None
            p2, cc = xs2
            y, cc2, _, _ = _apply_block(_ssd_view(cfg), p2, x2, cc, pos)
            return y, cc2

        if c_ssd is None:
            x, _ = U.scan(inner, x, p_unit)
            new_c_ssd = None
        else:
            x, new_c_ssd = U.scan(inner, x, (p_unit, c_ssd))

        # shared attention block (params closed over — reused every unit)
        a, new_c_attn = L.attention(cfg, shared["attn"],
                                    L.rms_norm(x, shared["ln1"]),
                                    cache=c_attn, pos=pos)
        x = x + a
        x = x + L.mlp(cfg, shared["mlp"], L.rms_norm(x, shared["ln2"]))
        if caches is None:
            return (x, aux), None
        return (x, aux), (new_c_ssd, new_c_attn)

    if caches is None:
        (x, aux), _ = U.scan(unit_body, (x, aux0), params["units"])
        x, _, _, _ = _scan_stack(_ssd_view(cfg), params["tail"], x, None,
                                 pos, remat)
        return x, None, aux
    (x, aux), (u_ssd, u_attn) = U.scan(
        unit_body, (x, aux0),
        (params["units"], caches["units_ssd"], caches["units_attn"]))
    x, tail_c, _, _ = _scan_stack(_ssd_view(cfg), params["tail"], x,
                                  caches["tail_ssd"], pos, remat)
    new_caches = {"units_ssd": u_ssd, "units_attn": u_attn,
                  "tail_ssd": tail_c}
    return x, new_caches, aux


@functools.lru_cache(maxsize=None)
def _ssd_view_cached(cfg):
    import dataclasses
    return dataclasses.replace(cfg, layout="ssd")


def _ssd_view(cfg):
    return _ssd_view_cached(cfg)


def forward(cfg: ModelConfig, params, batch_in, *, cache=None, pos=None,
            remat: str = "none", last_only: bool = False,
            stats: bool = False, capture: bool = False):
    """Returns (logits, new_cache, aux_loss), and the MoE ``stats`` (see
    ``_forward_stacks``) fourth when ``stats`` is set; ``capture`` adds
    each MoE layer's routed input, gate weights and routed output to them
    (``moe.moe_layer``).  ``last_only`` computes the head at the last
    position alone (logits ``[B, 1, V]``)."""
    from repro.launch.constrain import BATCH, constrain
    x = constrain(_embed(cfg, params, batch_in), BATCH)
    st = {}
    if cfg.layout == "zamba":
        x, new_cache, aux = _zamba_forward(cfg, params, x, cache, pos, remat)
    else:
        x, new_cache, aux, st = _forward_stacks(cfg, params, x, cache, pos,
                                                remat, capture)
    if last_only:
        x = x[:, -1:]
    out = (_head(cfg, params, x), new_cache, aux)
    return out + (st,) if stats else out


# ================================================================ entry points
def lm_loss(cfg: ModelConfig, logits, labels) -> jnp.ndarray:
    """Next-token CE.  labels already shifted; -100 = ignore."""
    valid = (labels >= 0)
    lab = jnp.maximum(labels, 0)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, lab[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)


def train_loss(cfg: ModelConfig, params, batch_in, remat: str = "full"):
    logits, _, aux = forward(cfg, params, batch_in, remat=remat)
    if cfg.input_mode == "tokens+patches":
        # loss over text positions only (patches are prompt context)
        logits = logits[:, cfg.patch_tokens:]
    loss = lm_loss(cfg, logits, batch_in["labels"])
    return loss + 0.01 * aux, {"lm_loss": loss, "aux_loss": aux}


def prefill(cfg: ModelConfig, params, batch_in, cache_len: int,
            stats: bool = False, capture: bool = False):
    """Build decode caches from a prompt.  Returns (logits, cache), the
    logits of the last position only (``[B, V]``, or ``[B, n_cb, V]``),
    and the MoE ``stats`` third when ``stats`` is set (with ``capture``'s
    additions, see ``forward``)."""
    B = batch_in["tokens"].shape[0]
    cache = init_cache(cfg, B, cache_len)
    logits, new_cache, _, st = forward(
        cfg, params, batch_in, cache=cache, pos=jnp.zeros((), jnp.int32),
        last_only=True, stats=True, capture=capture)
    out = (logits[:, 0], new_cache)
    return out + (st,) if stats else out


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step: tokens [B] (or [B, n_cb]), pos scalar int32.
    Returns (logits [B, V], new_cache)."""
    batch_in = {"tokens": tokens[:, None] if tokens.ndim == 1
                else tokens[:, None, :]}
    logits, new_cache, _ = forward(cfg, params, batch_in, cache=cache,
                                   pos=pos)
    return logits[:, 0], new_cache
