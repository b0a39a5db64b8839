"""DeepSeek-V2-Lite through the system's normal path against its plain
reference (``models.reference_deepseek_v2``), at smoke widths with the
leading dense layer, on the CPU; the dropless ``bcsr`` expert layer and
its device-built structure; YaRN against hand-worked values.

Tolerances: the system and the reference both compute in float32 here
(the smoke view runs the ``xla`` backend, or the Pallas interpreter), so
they differ only by summation order and XLA:CPU's fused kernels; 1e-4 on
logits of magnitude ~4 is ~30x that rounding and ~1e4x below a bf16
rounding of the activations (what a lower precision than the config's
would show)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import steps
from repro.models import layers as Lyr
from repro.models import moe as M
from repro.models import reference_deepseek_v2 as R
from repro.models import transformer as T

ATOL = 1e-4


def _cfg(**kw):
    return dataclasses.replace(get_config("deepseek-v2-lite-16b:smoke"),
                               dtype="float32", **kw)


def _tokens(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, (1, n))


# --------------------------------------------------------------- the model
def test_published_config():
    """The registered config is the published one (config.json)."""
    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.expert_d_ff) == \
        (27, 2048, 10944, 1408)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.n_shared_experts) == (64, 6, 2)
    assert cfg.first_k_dense_replace == 1 and not cfg.norm_topk_prob
    assert cfg.moe_dispatch == "bcsr" and cfg.routed_scaling_factor == 1.0
    y = cfg.rope_scaling
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == \
        (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    # 15.7B parameters, as published
    assert abs(cfg.param_count() - 15.7e9) < 0.05e9


def test_prefill_decode_match_reference():
    """Prefill's last-position logits and latent cache, then 3 decode
    steps through that cache, against the reference's full forward."""
    cfg = _cfg()
    params = T.init_params(cfg, seed=3)
    toks = _tokens(cfg, 40, 0)
    ref, det = R.forward(cfg, params, toks[0], details=True)
    ref, det = np.asarray(ref), jax.tree.map(np.asarray, det)

    prompt = 37
    logits, cache = steps.make_prefill_step(cfg, 40)(
        params, {"tokens": jnp.asarray(toks[:, :prompt], jnp.int32)})
    np.testing.assert_allclose(np.asarray(logits[0]), ref[prompt - 1],
                               atol=ATOL, rtol=0)
    ckv = np.concatenate([np.asarray(cache["dense_blocks"]["ckv"][:, 0]),
                          np.asarray(cache["blocks"]["ckv"][:, 0])])
    krope = np.concatenate([np.asarray(cache["dense_blocks"]["krope"][:, 0]),
                            np.asarray(cache["blocks"]["krope"][:, 0])])
    np.testing.assert_allclose(ckv[:, :prompt], det["ckv"][:, :prompt],
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(krope[:, :prompt], det["krope"][:, :prompt],
                               atol=ATOL, rtol=0)
    assert not ckv[:, prompt:].any()            # unwritten positions

    decode = steps.make_decode_step(cfg)
    for t in range(prompt, 40):
        logits, cache = decode(params, cache,
                               jnp.asarray(toks[:, t], jnp.int32),
                               jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits[0]), ref[t],
                                   atol=ATOL, rtol=0)


def test_prefill_head_at_last_position_only():
    """The prefill step's head runs on the last position alone: its
    program holds no ``[B, L, V]`` logits."""
    cfg = _cfg()
    params = T.param_specs(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    fn = steps.make_prefill_step(cfg, 64)
    logits, _ = jax.eval_shape(fn, params, batch)
    assert logits.shape == (2, cfg.vocab_size)
    text = jax.jit(fn).lower(params, batch).as_text()
    assert f"2x64x{cfg.vocab_size}" not in text
    assert f"2x1x{cfg.vocab_size}" in text


# ------------------------------------------------------- the expert layer
def _moe_inputs(cfg, seed, shape=(2, 24)):
    p = M.init_moe(cfg, jax.random.PRNGKey(seed), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          shape + (cfg.d_model,), jnp.float32)
    return p, x


def _reference_moe(cfg, p, x):
    B, L, D = x.shape
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.moe(cfg, p, x.reshape(B * L, D))).reshape(
            B, L, D)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_bcsr_layer_matches_expert_loop(backend):
    """The dropless layer equals a dense loop over the experts on the
    same routing, on the ``xla`` oracle and in the Pallas interpreter."""
    cfg = _cfg(moe_backend=backend)
    p, x = _moe_inputs(cfg, 0)
    y, _, stats = M.moe_layer(cfg, p, x, dispatch="bcsr",
                              interpret=backend == "pallas")
    np.testing.assert_allclose(np.asarray(y), _reference_moe(cfg, p, x),
                               atol=ATOL, rtol=0)
    assert int(stats["routed"]) == x.shape[0] * x.shape[1] * cfg.moe_top_k


def test_prefill_capture_holds_the_routed_experts():
    """``capture`` changes no output of the step and hands out, per MoE
    layer, the layer's input, routing and routed output: the routed
    output is the gate-weighted sum of each top-k expert's gated MLP on
    that input (the experts alone, without the shared ones)."""
    cfg = _cfg()
    params = T.init_params(cfg, seed=4)
    batch = {"tokens": jnp.asarray(_tokens(cfg, 16, 4))}
    logits, cache, stats = steps.make_prefill_step(cfg, 16, stats=True)(
        params, batch)
    logits_c, cache_c, got = steps.make_prefill_step(cfg, 16, capture=True)(
        params, batch)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_c))
    np.testing.assert_array_equal(np.asarray(stats["experts"]),
                                  np.asarray(got["experts"]))
    n_moe = cfg.n_layers - cfg.first_k_dense_replace
    assert got["routed_in"].shape == (n_moe, 1, 16, cfg.d_model)
    e, f, d = cfg.n_experts, cfg.expert_d_ff, cfg.d_model
    for layer in range(n_moe):
        p = jax.tree.map(lambda a: a[layer], params["blocks"]["moe"])
        wg, wu, wd = (p[k].reshape(e, f, d) for k in
                      ("w_gate", "w_up", "w_down"))
        x = np.asarray(got["routed_in"][layer, 0])
        want = np.zeros_like(x)
        for t in range(x.shape[0]):
            for j, ex in enumerate(np.asarray(got["experts"][layer, 0, t])):
                h = jax.nn.silu(wg[ex] @ x[t]) * (wu[ex] @ x[t])
                want[t] += float(got["weights"][layer, 0, t, j]) * \
                    np.asarray(h @ wd[ex])
        np.testing.assert_allclose(np.asarray(got["routed_out"][layer, 0]),
                                   want, atol=ATOL, rtol=0)
    with pytest.raises(ValueError):
        M.moe_layer(cfg, p, jnp.zeros((1, 4, d)), dispatch="gather",
                    capture=True)


def test_bcsr_full_skew_is_dropless():
    """Every token routed to expert 0 (and 1): the bcsr layer keeps every
    pair and equals the reference; ``gather`` at capacity factor 1.25
    drops the overflow."""
    cfg = _cfg()
    p, x = _moe_inputs(cfg, 5, shape=(2, 64))
    x = jnp.abs(x) + 1.0                       # every token: sum(x) > 0
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
    ref = _reference_moe(cfg, p, x)
    y, _, stats = M.moe_layer(cfg, p, x, dispatch="bcsr")
    np.testing.assert_allclose(np.asarray(y), ref, atol=ATOL, rtol=0)
    assert int(stats["rows_max"]) == 128       # all 2 x 64 tokens
    g_cfg = dataclasses.replace(cfg, moe_dispatch="gather")
    y_g, _ = M.moe_ffn(g_cfg, p, x, dispatch="gather")
    assert np.abs(np.asarray(y_g) - ref).max() > 100 * ATOL


def test_bcsr_matches_gather_without_drops():
    """With room for every token, ``gather`` and ``bcsr`` agree (weights
    in the bcsr layout are read by both)."""
    cfg = _cfg(capacity_factor=8.0)
    p, x = _moe_inputs(cfg, 2)
    y_b, aux_b = M.moe_ffn(cfg, p, x, dispatch="bcsr")
    y_g, aux_g = M.moe_ffn(cfg, p, x, dispatch="gather")
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_g), atol=ATOL)
    np.testing.assert_allclose(float(aux_b), float(aux_g), rtol=1e-6)


def test_bcsr_grads_flow():
    cfg = _cfg()
    p, x = _moe_inputs(cfg, 4, shape=(1, 16))

    def loss(p):
        y, aux = M.moe_ffn(cfg, p, x, dispatch="bcsr")
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p)
    for name in ("w_gate", "w_up", "w_down", "router"):
        assert float(jnp.abs(g[name]).sum()) > 0, name


@pytest.mark.parametrize("skew", [0.0, 0.9, 1.0])
def test_structure_invariants(skew):
    """Sorted block-rows, the static capacity, ``real_mask`` on exactly
    the used block-rows, every routed pair on one row of its expert's
    blocks in token order, and a nonempty transpose structure."""
    e, k, h, f, w = 8, 3, 16, 64, 32
    rng = np.random.default_rng(int(skew * 10))
    T_ = 50
    idx = np.stack([rng.permutation(e)[:k] for _ in range(T_)])
    hot = rng.random(T_) < skew
    idx[hot, 0] = 2                             # a hot expert
    idx[hot, 1:] = np.where(idx[hot, 1:] == 2, 0, idx[hot, 1:])
    rows = jax.tree.map(np.asarray, M.expert_rows(jnp.asarray(idx), e, h))
    counts = np.bincount(idx.ravel(), minlength=e)
    np.testing.assert_array_equal(rows["counts"], counts)
    r_cap = M.expert_capacity(T_ * k, e, h)
    assert rows["expert_of_block"].shape == (r_cap,)
    used = int(np.sum(-(-counts // h)))
    assert used <= r_cap
    np.testing.assert_array_equal(rows["real"], np.arange(r_cap) < used)
    rp = rows["row_of_pair"]
    assert len(set(rp.tolist())) == rp.size           # each pair once
    np.testing.assert_array_equal(rows["expert_of_block"][rp // h],
                                  idx.ravel())
    for ex in range(e):                                # token order kept
        assert np.all(np.diff(rp[idx.ravel() == ex]) == 1)
    arrays, meta = M.expert_structure(
        jax.tree.map(jnp.asarray, rows), e, f, (h, w))
    arrays = arrays._replace(**{n: np.asarray(getattr(arrays, n))
                                for n in arrays._fields[1:7]})
    assert meta.built == "device" and meta.nnzb == r_cap * f // w
    assert np.all(np.diff(arrays.row_ids) >= 0)
    np.testing.assert_array_equal(
        arrays.real_mask, np.repeat(np.arange(r_cap) < used, f // w))
    assert np.all(arrays.col_ids // (f // w) ==
                  np.repeat(rows["expert_of_block"], f // w))
    key = arrays.t_row_ids.astype(np.int64) * (r_cap + 1) + \
        np.where(arrays.t_perm == meta.nnzb, r_cap, arrays.t_col_ids)
    assert np.all(np.diff(key) > 0)                    # A^T row-major
    assert set(arrays.t_row_ids.tolist()) == set(range(meta.n_block_cols))


def test_capacity_bound_is_tight():
    """The capacity is reached: each of E experts one pair past a whole
    number of blocks."""
    e, h = 4, 8
    idx = np.concatenate([np.full(h + 1, ex) for ex in range(e)])[:, None]
    rows = M.expert_rows(jnp.asarray(idx), e, h)
    assert int(rows["real"].sum()) == 2 * e == \
        M.expert_capacity(idx.size, e, h) - 1


# --------------------------------------------------------------------- YaRN
def test_yarn_hand_worked():
    """DeepSeek-V2-Lite's YaRN at its rope dim 64: correction range
    [floor(64 ln(4096 / 64 pi) / 2 ln 1e4), ceil(64 ln(4096 / 2 pi) / 2 ln
    1e4)] = [10, 23]; below it the frequency is kept, above it divided by
    40, and at dim pair 16 (base frequency 1e4^-0.5 = 0.01, ramp 6/13) it
    is 0.01 * 7/13 + 0.00025 * 6/13 = 0.0055.  mscale(40, 0.707) =
    0.1 * 0.707 * ln 40 + 1 = 1.260804, so the softmax scale is
    192^-0.5 * 1.260804^2 = 0.114722."""
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    assert Lyr.yarn_correction_range(y, 64, 1e4) == (10, 23)
    f = Lyr.yarn_freqs(64, 1e4, y)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    assert f[16] == pytest.approx(0.0055, rel=1e-6)
    assert Lyr.yarn_mscale(40, 0.707) == pytest.approx(1.260804, abs=1e-6)
    assert Lyr.mla_softmax_scale(cfg) == pytest.approx(0.114722, abs=1e-6)
    assert Lyr.mla_softmax_scale(cfg) == pytest.approx(
        R.softmax_scale(cfg), rel=1e-12)
    # the reference's independent transcription of DeepSeek's code
    np.testing.assert_allclose(
        f, R.yarn_inv_freq(64, 1e4, 40, 32, 1, 4096), rtol=1e-6)
    # mscale == mscale_all_dim: cos / sin keep unit magnitude
    x = jnp.ones((1, 3, 1, 64))
    pos = jnp.arange(3)[None]
    out = Lyr.apply_rope(x, pos, 1e4, y)
    assert np.allclose(np.asarray(out[0, 0]), 1.0)    # position 0: identity
    assert math.isclose(Lyr.yarn_mscale(1.0, 0.707), 1.0)
