"""Plain DeepSeek-V2 forward pass: the reference the system is tested
against (``tests/test_deepseek_v2.py``).

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")``, one sequence at a time, with no kernels, cache or batching,
following DeepSeek's public ``modeling_deepseek.py``:

* MLA with the per-head K and V materialised from the latent and a causal
  softmax, scaled by ``q_head_dim ** -0.5 * mscale(factor,
  mscale_all_dim) ** 2``;
* YaRN rotary embedding (``DeepseekV2YarnRotaryEmbedding``,
  ``yarn_get_mscale``, ``yarn_find_correction_range``,
  ``yarn_linear_ramp_mask``);
* ``first_k_dense_replace`` dense gated-SiLU layers, then MoE layers:
  softmax router in float32, greedy top-k, weights renormalised only when
  ``norm_topk_prob``, times ``routed_scaling_factor``; each routed expert
  applied to the tokens routed to it, in a loop over the experts; the
  shared experts applied to every token.

Departures, each a relabelling that random weights cannot tell apart:

* the rope dims are rotated in the rotate-half layout; DeepSeek's
  interleaved layout (``view(..., d // 2, 2).transpose``) is a fixed
  permutation of the rope columns of ``wq`` and ``wkv_a``;
* RMSNorm scales by ``1 + w`` (the program's parametrisation), where
  DeepSeek scales by ``w``;
* the routed experts' weights are read in the program's ``[E * F, D]``
  layout (``models.moe.init_moe`` under ``bcsr``) or its ``[E, D, F]``
  one.

It reads the program's parameter tree by name and imports nothing else of
the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def rms_norm(x, w):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * \
        (1.0 + w.astype(jnp.float32))


def yarn_get_mscale(scale: float = 1.0, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(num_rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / \
        (2 * math.log(base))


def yarn_inv_freq(dim: int, base: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max_pos: int) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding.inv_freq``, in float32."""
    ar = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    freq_extra = np.float32(1.0) / (np.float32(base) ** ar)
    freq_inter = np.float32(1.0) / (np.float32(factor) *
                                    np.float32(base) ** ar)
    low = max(math.floor(_correction_dim(beta_fast, dim, base,
                                         original_max_pos)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, base,
                                         original_max_pos)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) /
                   (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return (freq_inter * (1 - inv_freq_mask) +
            freq_extra * inv_freq_mask).astype(np.float32)


def rope(x, positions, cfg):
    """x [L, H, d] rotated at ``positions`` [L] (rotate-half layout)."""
    d = x.shape[-1]
    ys = cfg.rope_scaling
    if ys is None:
        inv_freq = 1.0 / cfg.rope_theta ** (
            np.arange(0, d, 2, dtype=np.float32) / d)
        mag = 1.0
    else:
        inv_freq = yarn_inv_freq(d, cfg.rope_theta, ys.factor, ys.beta_fast,
                                 ys.beta_slow,
                                 ys.original_max_position_embeddings)
        mag = yarn_get_mscale(ys.factor, ys.mscale) / \
            yarn_get_mscale(ys.factor, ys.mscale_all_dim)
    freqs = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    emb = jnp.concatenate([freqs, freqs], -1)             # [L, d]
    cos, sin = (jnp.cos(emb) * mag)[:, None], (jnp.sin(emb) * mag)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * cos + rotated * sin


def softmax_scale(cfg) -> float:
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = yarn_get_mscale(ys.factor, ys.mscale_all_dim)
        scale = scale * m * m
    return scale


def _f32(p):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)


def mla(cfg, p, x, positions):
    """x [L, D] -> (y [L, D], the latent ``c_kv`` [L, r] and the rotated
    ``k_pe`` [L, rope] a decode cache holds); causal over the sequence."""
    L = x.shape[0]
    h, nope, rope_d, vd, r = (cfg.n_heads, cfg.nope_head_dim,
                              cfg.rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(L, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[:, :r], p["kv_norm"])
    k_pe = kv_a[:, r:].reshape(L, 1, rope_d)
    kv = (c_kv @ p["wkv_b"]).reshape(L, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rope(q_pe, positions, cfg)
    k_pe = rope(k_pe, positions, cfg)
    qq = jnp.concatenate([q_nope, q_pe], -1)              # [L, h, 192]
    kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (L, h, rope_d))],
                         -1)
    scores = jnp.einsum("qhd,khd->hqk", qq, kk) * softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(L, h * vd)
    return out @ p["wo"], c_kv, k_pe[:, 0]


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_weights(cfg, p):
    """Routed experts as ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``."""
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if wg.ndim == 2:                       # [E * F, D]
        e, f, d = cfg.n_experts, cfg.expert_d_ff, cfg.d_model
        wg = wg.reshape(e, f, d).transpose(0, 2, 1)
        wu = wu.reshape(e, f, d).transpose(0, 2, 1)
        wd = wd.reshape(e, f, d)
    return wg, wu, wd


def route(cfg, p, x):
    """(top-k weights [T, k], top-k experts [T, k])."""
    scores = jax.nn.softmax(x @ p["router"], -1)
    w, idx = jax.lax.top_k(scores, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


def moe(cfg, p, x):
    """x [T, D] -> [T, D]: each expert on the tokens routed to it."""
    w, idx = route(cfg, p, x)
    wg, wu, wd = expert_weights(cfg, p)
    idx_np = np.asarray(idx)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        tok, slot = np.nonzero(idx_np == e)
        if tok.size == 0:
            continue
        out = gated_mlp(x[tok], wg[e], wu[e], wd[e]) * w[tok, slot][:, None]
        y = y.at[tok].add(out)
    if cfg.n_shared_experts:
        s = p["shared"]
        y = y + gated_mlp(x, s["w_gate"], s["w_up"], s["w_down"])
    return y


def _layer(stack, i):
    return jax.tree.map(lambda a: a[i], stack)


def forward(cfg, params, tokens, *, details: bool = False):
    """Logits ``[L, V]`` of one sequence ``tokens`` [L].  With
    ``details``, also ``{"routes": [n_moe_layers, L, k] top-k experts,
    "ckv": [n_layers, L, r], "krope": [n_layers, L, rope]}``: the routing
    choices (to count against the program's) and the latent cache."""
    out = {"routes": [], "ckv": [], "krope": []}
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens)
        positions = jnp.arange(tokens.shape[0])
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        k = cfg.first_k_dense_replace
        for i in range(cfg.n_layers):
            dense = i < k
            p = _f32(_layer(params["dense_blocks"], i) if dense
                     else _layer(params["blocks"], i - k))
            a, c_kv, k_pe = mla(cfg, p["mla"], rms_norm(x, p["ln1"]),
                                positions)
            out["ckv"].append(c_kv)
            out["krope"].append(k_pe)
            x = x + a
            hn = rms_norm(x, p["ln2"])
            if dense:
                m = p["mlp"]
                x = x + gated_mlp(hn, m["w_gate"], m["w_up"], m["w_down"])
            else:
                out["routes"].append(route(cfg, p["moe"], hn)[1])
                x = x + moe(cfg, p["moe"], hn)
        x = rms_norm(x, params["final_norm"])
        logits = x @ jnp.asarray(params["lm_head"], jnp.float32)
    if not details:
        return logits
    return logits, {k: jnp.stack(v) for k, v in out.items()}
