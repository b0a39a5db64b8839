"""Plain DeepSeek-V2 forward pass of one sequence, layer by layer, and the
random weights of the ``deepseek-v2-lite`` cells in the published layout.

Weights (``draw_ends``, ``draw_layer``): drawn from the run's seed, one
layer at a time, in bfloat16 under the names and shapes of the published
checkpoint (``model.layers.<i>.``, as ``modeling_deepseek.py`` lays it
out; a ``torch.nn.Linear`` weight is ``[out, in]``), with one liberty:
each routed expert's ``gate_proj``/``up_proj``/``down_proj`` weight
stands in an ``mlp.experts.<name>.weight`` stack, expert ``e`` at index
``e``.  Matrices have std ``in ** -0.5``, the embedding 0.02, norm
weights ``1 + 0.1 N(0, 1)``.  A jitted draw gives the same bits each time
it is called, so the check draws each layer again rather than keep it.

Forward (``forward``): every product and sum in float32 at
``Precision.HIGHEST``, following DeepSeek's public ``modeling_deepseek.py``:
MLA with the per-head K and V materialised from the latent, the rope
dims de-interleaved before their rotation (``apply_rotary_pos_emb``) and
a causal softmax scaled by ``q_head_dim ** -0.5 * yarn_get_mscale(factor,
mscale_all_dim) ** 2``; YaRN rotary frequencies
(``DeepseekV2YarnRotaryEmbedding``); the leading dense gated-SiLU layers;
then MoE layers with a float32 softmax router, greedy top-k, the weights
renormalised under ``norm_topk_prob`` or else scaled by
``routed_scaling_factor``, each routed expert applied to the tokens routed
to it (a loop over the experts, tokens not routed to the expert weighted
by an exact 0), and the shared experts on every token.  ``routed`` is the
routed experts alone, on a routing given.

``precision="fp8"`` is the control: weights and every matmul's inputs
rounded to float8 (e4m3), the step below the configuration's bfloat16.

It imports nothing of the program and reads the sizes from the
configuration file's published keys.  Weights go to float32 one layer at
a time, so the reference fits beside the program's weights.  No
departure from DeepSeek's forward pass; ``q_lora_rank`` must be null, as
in V2-Lite.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import seeds

HIGHEST = jax.lax.Precision.HIGHEST
ROUNDING = {"f32": None, "fp8": jnp.float8_e4m3fn}


def _round(x, precision):
    dt = ROUNDING[precision]
    x = x.astype(jnp.float32)
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(a, b, precision):
    """``a @ b.T``: ``b`` a ``[out, in]`` weight, as published."""
    return jnp.matmul(_round(a, precision), _round(b, precision).T,
                      precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


# ------------------------------------------------------------------ weights
def _items(c: dict):
    """The configuration as a hashable static argument."""
    return tuple(sorted((k, (tuple(sorted(v.items())) if isinstance(v, dict)
                             else v)) for k, v in c.items()
                        if not isinstance(v, list)))


def _config(items) -> dict:
    c = dict(items)
    if isinstance(c.get("rope_scaling"), tuple):
        c["rope_scaling"] = dict(c["rope_scaling"])
    return c


class _Draw:
    """Named bfloat16 arrays, each from a key of its own."""

    def __init__(self, key):
        self.key, self.out = key, {}

    def _next(self):
        self.key, k = jax.random.split(self.key)
        return k

    def matrix(self, name, shape):
        x = jax.random.normal(self._next(), shape, jnp.float32)
        self.out[name] = (x * shape[-1] ** -0.5).astype(jnp.bfloat16)

    def norm(self, name, n):
        x = jax.random.normal(self._next(), (n,), jnp.float32)
        self.out[name] = (1.0 + 0.1 * x).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _draw_ends(key, *, cfg_items):
    c = _config(cfg_items)
    v, d = c["vocab_size"], c["hidden_size"]
    dr = _Draw(key)
    dr.out["model.embed_tokens.weight"] = (jax.random.normal(
        dr._next(), (v, d), jnp.float32) * 0.02).astype(jnp.bfloat16)
    dr.norm("model.norm.weight", d)
    dr.matrix("lm_head.weight", (v, d))
    return dr.out


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _draw_layer(key, *, cfg_items, kind):
    c = _config(cfg_items)
    if c.get("q_lora_rank"):
        raise ValueError("q_lora_rank: only the V2-Lite layout is drawn")
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rd, vd, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                       c["v_head_dim"], c["kv_lora_rank"])
    dr = _Draw(key)
    dr.norm("input_layernorm.weight", d)
    dr.matrix("self_attn.q_proj.weight", (h * (nope + rd), d))
    dr.matrix("self_attn.kv_a_proj_with_mqa.weight", (r + rd, d))
    dr.norm("self_attn.kv_a_layernorm.weight", r)
    dr.matrix("self_attn.kv_b_proj.weight", (h * (nope + vd), r))
    dr.matrix("self_attn.o_proj.weight", (d, h * vd))
    dr.norm("post_attention_layernorm.weight", d)
    if kind == "dense":
        f = c["intermediate_size"]
        dr.matrix("mlp.gate_proj.weight", (f, d))
        dr.matrix("mlp.up_proj.weight", (f, d))
        dr.matrix("mlp.down_proj.weight", (d, f))
        return dr.out
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = f * c["n_shared_experts"]
    dr.matrix("mlp.gate.weight", (e, d))
    dr.matrix("mlp.experts.gate_proj.weight", (e, f, d))
    dr.matrix("mlp.experts.up_proj.weight", (e, f, d))
    dr.matrix("mlp.experts.down_proj.weight", (e, d, f))
    dr.matrix("mlp.shared_experts.gate_proj.weight", (fs, d))
    dr.matrix("mlp.shared_experts.up_proj.weight", (fs, d))
    dr.matrix("mlp.shared_experts.down_proj.weight", (d, fs))
    return dr.out


def layer_kind(config: dict, i: int) -> str:
    return "dense" if i < config["first_k_dense_replace"] else "moe"


def draw_ends(config: dict, seed: int) -> dict:
    """The embedding, final norm and LM head of the run ``seed``."""
    return _draw_ends(seeds.jax_key(seed, "weights"),
                      cfg_items=_items(config))


def draw_layer(config: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s weights of the run ``seed``, named as in the
    checkpoint after ``model.layers.<i>.``."""
    key = jax.random.fold_in(seeds.jax_key(seed, "weights"), i + 1)
    return _draw_layer(key, cfg_items=_items(config),
                       kind=layer_kind(config, i))


# ------------------------------------------------------------------ forward
def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(c: dict) -> np.ndarray:
    """The rope inverse frequencies (YaRN when ``rope_scaling`` says so)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    ar = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = np.float32(1.0) / (np.float32(base) ** ar)
    ys = c.get("rope_scaling")
    if not ys:
        return extra
    factor = float(ys["factor"])
    inter = np.float32(1.0) / (np.float32(factor) * np.float32(base) ** ar)

    def corr(rot):
        return dim * math.log(ys["original_max_position_embeddings"] /
                              (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope_mag(c: dict) -> float:
    ys = c.get("rope_scaling")
    if not ys:
        return 1.0
    return _mscale(ys["factor"], ys["mscale"]) / \
        _mscale(ys["factor"], ys["mscale_all_dim"])


def softmax_scale(c: dict) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    ys = c.get("rope_scaling")
    if ys and ys.get("mscale_all_dim"):
        m = _mscale(ys["factor"], ys["mscale_all_dim"])
        scale = scale * m * m
    return scale


def _rope(x, positions, freqs, mag):
    """``apply_rotary_pos_emb``: the interleaved pairs ``(2i, 2i + 1)``
    de-interleaved to halves, then rotated by half."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    f = positions.astype(jnp.float32)[:, None] * freqs
    emb = jnp.concatenate([f, f], -1)
    cos, sin = (jnp.cos(emb) * mag)[:, None], (jnp.sin(emb) * mag)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def _mla(c, p, x, precision):
    L = x.shape[0]
    h, nope, rd, vd, r = (c["num_attention_heads"], c["qk_nope_head_dim"],
                          c["qk_rope_head_dim"], c["v_head_dim"],
                          c["kv_lora_rank"])
    positions = jnp.arange(L)
    freqs, mag = jnp.asarray(inv_freq(c)), _rope_mag(c)
    q = _mm(x, p["self_attn.q_proj.weight"], precision).reshape(
        L, h, nope + rd)
    kv_a = _mm(x, p["self_attn.kv_a_proj_with_mqa.weight"], precision)
    c_kv = _rms(kv_a[:, :r], p["self_attn.kv_a_layernorm.weight"],
                c["rms_norm_eps"])
    k_pe = _rope(kv_a[:, r:].reshape(L, 1, rd), positions, freqs, mag)
    q_pe = _rope(q[..., nope:], positions, freqs, mag)
    kv = _mm(c_kv, p["self_attn.kv_b_proj.weight"], precision).reshape(
        L, h, nope + vd)
    qq = jnp.concatenate([q[..., :nope], q_pe], -1)
    kk = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (L, h, rd))], -1)
    s = jnp.einsum("qhd,khd->hqk", _round(qq, precision),
                   _round(kk, precision), precision=HIGHEST)
    s = s * softmax_scale(c)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    probs = jax.nn.softmax(s, -1)
    o = jnp.einsum("hqk,khd->qhd", _round(probs, precision),
                   _round(kv[..., nope:], precision), precision=HIGHEST)
    return _mm(o.reshape(L, h * vd), p["self_attn.o_proj.weight"], precision)


def _gated(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def _route(c, router, x, precision):
    scores = jax.nn.softmax(_mm(x, router, precision), -1)
    w, idx = jax.lax.top_k(scores, c["num_experts_per_tok"])
    if c["num_experts_per_tok"] > 1 and c["norm_topk_prob"]:
        return w / (w.sum(-1, keepdims=True) + 1e-20), idx
    return w * c["routed_scaling_factor"], idx


def _routed(c, p, x, w, idx, precision):
    """The routed experts of a MoE layer on tokens ``x`` [T, D] routed to
    experts ``idx`` [T, k] with gate weights ``w`` [T, k]."""
    e = c["n_routed_experts"]
    # gate weight of each expert for each token, 0 where not routed
    weight = jnp.zeros((x.shape[0], e), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(w.astype(jnp.float32))
    wg, wu, wd = (p[f"mlp.experts.{n}.weight"]
                  for n in ("gate_proj", "up_proj", "down_proj"))

    def expert(i, y):
        out = _gated(x, wg[i], wu[i], wd[i], precision)
        return y + out * weight[:, i][:, None]
    return jax.lax.fori_loop(0, e, expert, jnp.zeros_like(x))


def _moe(c, p, x, precision):
    """Routed experts in a loop, then the shared ones; also the top-k."""
    w, idx = _route(c, p["mlp.gate.weight"], x, precision)
    y = _routed(c, p, x, w, idx, precision)
    return y + _gated(x, p["mlp.shared_experts.gate_proj.weight"],
                      p["mlp.shared_experts.up_proj.weight"],
                      p["mlp.shared_experts.down_proj.weight"],
                      precision), idx


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind",
                                             "precision"))
def _layer(p, x, *, cfg_items, kind, precision):
    c = _config(cfg_items)
    p = jax.tree.map(lambda a: _round(a, precision), p)
    eps = c["rms_norm_eps"]
    x = x + _mla(c, p, _rms(x, p["input_layernorm.weight"], eps), precision)
    hn = _rms(x, p["post_attention_layernorm.weight"], eps)
    if kind == "dense":
        return x + _gated(hn, p["mlp.gate_proj.weight"],
                          p["mlp.up_proj.weight"], p["mlp.down_proj.weight"],
                          precision), None
    y, idx = _moe(c, p, hn, precision)
    return x + y, idx


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _routed_layer(p, x, w, idx, *, cfg_items, precision):
    p = jax.tree.map(lambda a: _round(a, precision), p)
    return _routed(_config(cfg_items), p, x.astype(jnp.float32), w, idx,
                   precision)


def routed(config: dict, seed: int, i: int, x, w, idx, *,
           precision: str = "f32"):
    """``[T, D]``: the routed experts of MoE layer ``i`` (its index among
    all layers) of the run ``seed``, on tokens ``x`` [T, D] routed to
    ``idx`` [T, k] with gate weights ``w`` [T, k]."""
    return _routed_layer(draw_layer(config, seed, i), x, w, idx,
                         cfg_items=_items(config), precision=precision)


def forward(config: dict, seed: int, tokens, last: int, *,
            precision: str = "f32"):
    """Logits ``[last, V]`` of the sequence's last ``last`` positions, and
    the top-k experts ``[n_moe_layers, L, k]`` of each MoE layer, with the
    weights of the run ``seed``."""
    items = _items(config)
    c = _config(items)
    ends = draw_ends(config, seed)
    x = ends["model.embed_tokens.weight"][jnp.asarray(tokens)].astype(
        jnp.float32)
    routes = []
    for i in range(c["num_hidden_layers"]):
        kind = layer_kind(c, i)
        x, idx = _layer(draw_layer(config, seed, i), x, cfg_items=items,
                        kind=kind, precision=precision)
        if idx is not None:
            routes.append(idx)
    x = _rms(x[-last:], ends["model.norm.weight"], c["rms_norm_eps"])
    logits = _mm(x, ends["lm_head.weight"], precision)
    return logits, jnp.stack(routes)
