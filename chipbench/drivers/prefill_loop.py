"""Model prefill cells: one caller in a closed loop around the program's
prefill step.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``batch``, ``seq_len``: prompts per step and tokens per prompt;
* ``batches``: how many seeded token batches the steps take in turn;
* ``zipf``: the exponent of the token ids' law (ranks permuted from the
  seed, so the ids most drawn differ from seed to seed);
* ``sample``: how many of the window's prompts the check compares;
* ``decode_steps``: the decode steps the check runs after each compared
  prompt, through the latent cache the timed step wrote.

The model is the configuration file's published keys, given to the
program's registered architecture (``"arch"``).  Its weights are drawn
from the seed in the published checkpoint's layout by the reference
module (``chipbench/reference/deepseek_v2.py``) and loaded into the
program's parameter tree by ``to_program``, the one conversion, which the
reference never uses.  Each step is the program's
``launch.steps.make_prefill_step`` (last-position logits and the decode
cache), waited for before the next.

The check, after the window, for each sampled prompt:

* ``logit_gap``: the largest absolute difference between the program's
  logits (the timed step's last position, then ``decode_steps`` steps of
  ``launch.steps.make_decode_step`` through the latent cache it wrote,
  fed seeded tokens) and the reference's forward pass of the extended
  sequence;
* ``routed_rel_err``: the routed experts alone.  The prompt's step is run
  again with ``capture``, which returns each MoE layer's input, routing
  and routed output; the reference applies its experts to that input on
  that routing, and each layer reads ``max |program - reference| / max
  |reference|``.  With 64 experts each routed weight is a few hundredths,
  so the logits barely see the routed part; this check holds it alone.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts_models, seeds
from chipbench.harness import Check
from chipbench.reference import deepseek_v2 as ref_model

# published key of the configuration file -> field of the program's config
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
    "qk_rope_head_dim": "rope_head_dim", "qk_nope_head_dim": "nope_head_dim",
    "v_head_dim": "v_head_dim", "n_routed_experts": "n_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "expert_d_ff", "rope_theta": "rope_theta",
    "first_k_dense_replace": "first_k_dense_replace",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
}
YARN = ("factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")


class State:
    pass


def model_config(config: dict, opts):
    """The program's config of the configuration file: its registered
    architecture with every published key of the file set.  On the CPU
    (the tests, ``opts.interpret``) the expert products run on the ``xla``
    oracle."""
    from repro.configs import get_config
    from repro.configs.base import YarnRope
    cfg = get_config(config["arch"])
    kw = {field: config[key] for key, field in FIELDS.items()}
    ys = config["rope_scaling"]
    if ys.get("type") != "yarn":
        raise ValueError(f"rope_scaling {ys!r}: only yarn is run")
    kw["rope_scaling"] = YarnRope(**{k: ys[k] for k in YARN})
    kw["dtype"] = config["dtype"]
    kw["moe_dispatch"] = config["moe_dispatch"]
    if opts is not None and opts.interpret:
        kw["moe_backend"] = "xla"
    return dataclasses.replace(cfg, **kw)


def _zipf_tokens(rng, shape, vocab: int, a: float) -> np.ndarray:
    """Ids drawn with probability proportional to ``rank ** -a``, the
    ranks a permutation of the ids drawn from ``rng``."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    ranks = rng.choice(vocab, size=shape, p=p / p.sum())
    return rng.permutation(vocab).astype(np.int32)[ranks]


def _norm(w):
    """A published RMSNorm weight (``x * w``) as the program's scale
    (``x * (1 + scale)``, float32)."""
    return w.astype(jnp.float32) - 1.0


def _rope_rows(w, start: int, rd: int):
    """``w`` with the rope rows ``start .. start + rd`` of its first axis
    reordered from the published interleaved pairs ``(2i, 2i + 1)`` to the
    program's halves ``(i, i + rd / 2)``."""
    perm = np.concatenate([np.arange(0, rd, 2), np.arange(1, rd, 2)])
    return jnp.concatenate([w[:start], w[start:start + rd][perm],
                            w[start + rd:]], 0)


def to_program(c: dict, hf: dict, kind: str) -> dict:
    """One layer of the published layout (``chipbench/reference/
    deepseek_v2.py``'s ``draw_layer``) as a block of the program's tree:
    ``[out, in]`` weights transposed to ``[in, out]``; the rope rows of
    ``q_proj`` (each head's last ``qk_rope_head_dim``) and of
    ``kv_a_proj_with_mqa`` put in the program's rotate-half order; norm
    weights as ``scale = w - 1``; the router in float32; each routed
    expert's ``gate_proj``/``up_proj`` ``[F, D]`` and ``down_proj``
    ``[D, F]`` transposed stacked as the ``bcsr`` dispatch's ``[E * F, D]``
    (``repro.models.moe.init_moe``)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rd, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                   c["kv_lora_rank"])
    q = hf["self_attn.q_proj.weight"].reshape(h, nope + rd, d)
    q = jax.vmap(lambda w: _rope_rows(w, nope, rd))(q).reshape(-1, d)
    kv_a = _rope_rows(hf["self_attn.kv_a_proj_with_mqa.weight"], r, rd)
    block = {
        "ln1": _norm(hf["input_layernorm.weight"]),
        "mla": {"wq": q.T, "wkv_a": kv_a.T,
                "kv_norm": _norm(hf["self_attn.kv_a_layernorm.weight"]),
                "wkv_b": hf["self_attn.kv_b_proj.weight"].T,
                "wo": hf["self_attn.o_proj.weight"].T},
        "ln2": _norm(hf["post_attention_layernorm.weight"])}

    def gated(prefix):
        return {"w_gate": hf[f"{prefix}.gate_proj.weight"].T,
                "w_up": hf[f"{prefix}.up_proj.weight"].T,
                "w_down": hf[f"{prefix}.down_proj.weight"].T}
    if kind == "dense":
        block["mlp"] = gated("mlp")
        return block
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    block["moe"] = {
        "router": hf["mlp.gate.weight"].T.astype(jnp.float32),
        "w_gate": hf["mlp.experts.gate_proj.weight"].reshape(e * f, d),
        "w_up": hf["mlp.experts.up_proj.weight"].reshape(e * f, d),
        "w_down": hf["mlp.experts.down_proj.weight"].transpose(
            0, 2, 1).reshape(e * f, d),
        "shared": gated("mlp.shared_experts")}
    return block


def _put_layer(c, kind, stack, hf, j):
    """``stack`` with layer ``j`` set to ``hf`` in the program's layout."""
    return jax.tree.map(
        lambda s, v: jax.lax.dynamic_update_index_in_dim(
            s, v.astype(s.dtype), j, 0),
        stack, to_program(c, hf, kind))


def _ends_to_program(hf):
    return {"embed": hf["model.embed_tokens.weight"],
            "final_norm": _norm(hf["model.norm.weight"]),
            "lm_head": hf["lm_head.weight"].T}


def load_weights(config: dict, cfg, seed: int) -> dict:
    """The program's parameters of the run ``seed``: the published layers
    drawn one at a time and written into the stacks in place, so one layer
    is held twice at most.  Every leaf has the shape and dtype of the
    program's ``init_params``, or this raises."""
    from repro.models import transformer as T
    target = jax.eval_shape(functools.partial(T.init_params, cfg))
    params = jax.jit(_ends_to_program)(ref_model.draw_ends(config, seed))
    first = 0
    for name, kind in (("dense_blocks", "dense"), ("blocks", "moe")):
        if name not in target:
            continue
        stack = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             target[name])
        put = jax.jit(functools.partial(_put_layer, config, kind),
                      donate_argnums=0)
        n = jax.tree.leaves(stack)[0].shape[0]
        for j in range(n):
            stack = put(stack, ref_model.draw_layer(config, seed, first + j),
                        j)
        params[name] = stack
        first += n
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), target)
    if got != want:
        raise ValueError("the loaded weights are not the program's tree")
    return params


def setup(cell, seed: int, opts) -> State:
    from repro.launch import steps

    cfg_file, traffic = cell.config, cell.traffic
    st = State()
    st.config, st.seed = cfg_file, seed
    st.cfg = model_config(cfg_file, opts)
    st.limits = cfg_file["checks"]
    st.batch, st.seq = int(traffic["batch"]), int(traffic["seq_len"])
    st.decode_steps = int(traffic["decode_steps"])
    rng = np.random.default_rng(seeds.numpy_seed(seed, "tokens"))
    n = int(traffic["batches"])
    toks = _zipf_tokens(rng, (n, st.batch, st.seq + st.decode_steps),
                        st.cfg.vocab_size, float(traffic["zipf"]))
    st.prompts = toks[..., : st.seq]
    st.extensions = toks[..., st.seq:]            # the check's decode feed
    st.batches = [jax.device_put({"tokens": jnp.asarray(b)})
                  for b in st.prompts]
    st.params = load_weights(cfg_file, st.cfg, seed)
    jax.block_until_ready(st.params)
    st.fn = jax.jit(steps.make_prefill_step(st.cfg, st.seq, stats=True))
    for b in st.batches[:2]:                      # compile, then warm
        logits, cache, stats = st.fn(st.params, b)
        jax.block_until_ready(_prompt(logits, cache, stats["experts"], 0))
    del logits, cache, stats
    st.rng = np.random.default_rng(seeds.numpy_seed(seed, "sample"))
    st.sample_size = int(traffic["sample"])
    st.record = {"batch": st.batch, "seq_len": st.seq,
                 "flops_per_step": counts_models.prefill_flops(
                     cfg_file, st.batch, st.seq),
                 "moe": counts_models.moe_expert_counts(
                     cfg_file, st.batch * st.seq)}
    return st


@jax.jit
def _prompt(logits, cache, experts, s):
    """Prompt ``s`` of a step's outputs: its logits, its slice of the
    cache (batch axis 1, after the layers) and of the routing."""
    def take(a):
        return jax.lax.dynamic_slice_in_dim(a, s, 1, axis=1)
    return logits[s], jax.tree.map(take, cache), take(experts)[:, 0]


def _keep(st, step: int, b: int, logits, cache, stats) -> dict:
    """What the check needs of one prompt of a step, on the device."""
    s = int(st.rng.integers(0, st.batch))
    lg, c, ex = _prompt(logits, cache, stats["experts"], s)
    return {"step": step, "batch": b, "seq": s, "logits": lg, "cache": c,
            "experts": ex}


def window(st: State, seconds: float) -> dict:
    """Steps until ``seconds`` have passed; a reservoir sample, drawn from
    the seed, of the steps keeps ``sample`` prompts for the check."""
    from repro.models import moe as M
    sample = []
    steps = 0
    t_open = time.perf_counter()
    t_end = t_open
    while t_end - t_open < seconds:
        b = steps % len(st.batches)
        with jax.profiler.TraceAnnotation("bench.step"):
            logits, cache, stats = st.fn(st.params, st.batches[b])
            jax.block_until_ready((logits, cache))
        t_end = time.perf_counter()
        M.record_stats(jax.device_get(
            {k: v for k, v in stats.items() if k != "experts"}))
        if len(sample) < st.sample_size:
            sample.append(_keep(st, steps, b, logits, cache, stats))
        else:
            j = int(st.rng.integers(0, steps + 1))
            if j < st.sample_size:
                sample[j] = _keep(st, steps, b, logits, cache, stats)
        steps += 1
    return {"calls": steps, "seconds": t_end - t_open, "sample": sample,
            "attempted": steps, "failed": 0}


def free(st: State) -> None:
    """Drop the program's step; the weights stay for the check."""
    del st.fn, st.batches


def _extend(cache, length: int):
    """The cache's sequence axis (2, after layers and batch) padded to
    ``length`` positions, room for the decode steps."""
    def pad(a):
        return jnp.pad(a, [(0, 0), (0, 0), (0, length - a.shape[2])] +
                       [(0, 0)] * (a.ndim - 3))
    return jax.tree.map(pad, cache)


def _program_logits(st, kept, decode) -> np.ndarray:
    """``[1 + decode_steps, V]``: the timed step's logits, then those of
    the decode steps through its cache."""
    cache = _extend(kept["cache"], st.seq + st.decode_steps)
    out = [np.asarray(kept["logits"], np.float32)]
    feed = st.extensions[kept["batch"], kept["seq"]]
    for t in range(st.decode_steps):
        logits, cache = decode(st.params, cache,
                               jnp.asarray(feed[t:t + 1]),
                               jnp.asarray(st.seq + t, jnp.int32))
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)


def _routed_err(got, ref) -> float:
    ref = np.asarray(ref, np.float32)
    gap = np.max(np.abs(np.asarray(got, np.float32) - ref))
    return float(gap / max(float(np.max(np.abs(ref))), 1e-30))


def _routed_check(st, captured, s: int, variant: str) -> float:
    """The largest ``routed_rel_err`` of prompt ``s``'s MoE layers, from a
    ``capture`` run of its step."""
    worst = 0.0
    first = st.config["first_k_dense_replace"]
    for layer in range(captured["routed_out"].shape[0]):
        x, w, idx = (captured[k][layer, s]
                     for k in ("routed_in", "weights", "experts"))
        ref = ref_model.routed(st.config, st.seed, first + layer, x, w, idx)
        if variant == "control":
            got = ref_model.routed(st.config, st.seed, first + layer, x, w,
                                   idx, precision="fp8")
        else:
            got = captured["routed_out"][layer, s]
        err = _routed_err(got, ref)
        worst = max(worst, err if math.isfinite(err) else math.inf)
    return worst


def check(st: State, win: dict, opts) -> list:
    from repro.launch import steps
    decode = jax.jit(steps.make_decode_step(st.cfg))
    step = steps.make_prefill_step(st.cfg, st.seq, capture=True)
    capture = jax.jit(lambda params, tokens: step(
        params, {"tokens": tokens})[2])
    worst, routed_worst, differ, choices = 0.0, 0.0, 0, 0
    n = 1 + st.decode_steps
    for kept in win["sample"]:
        b, s = kept["batch"], kept["seq"]
        tokens = np.concatenate([st.prompts[b, s], st.extensions[b, s]])
        ref, routes = ref_model.forward(st.config, st.seed, tokens, n)
        ref = np.asarray(ref)
        if opts.variant == "control":
            got = np.asarray(ref_model.forward(st.config, st.seed, tokens,
                                               n, precision="fp8")[0])
        else:
            got = _program_logits(st, kept, decode)
            ours = np.sort(np.asarray(kept["experts"]), -1)
            theirs = np.sort(np.asarray(routes)[:, : st.seq], -1)
            differ += int(np.sum(np.any(ours != theirs, -1)))
            choices += ours.shape[0] * ours.shape[1]
        gap = float(np.max(np.abs(got - ref)))
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
        captured = capture(st.params, jnp.asarray(st.prompts[b]))
        routed_worst = max(routed_worst,
                           _routed_check(st, captured, s, opts.variant))
        del captured
    if choices:
        print(f"chipbench: routing differs from the reference on {differ} "
              f"of {choices} (layer, token) top-k choices", file=sys.stderr,
              flush=True)
    return [Check("logit_gap", worst, float(st.limits["logit_gap"])),
            Check("routed_rel_err", routed_worst,
                  float(st.limits["routed_rel_err"]))]


def end_to_end(st: State, win: dict) -> dict:
    flops = st.record["flops_per_step"] * win["calls"]
    return {"lib_gflops": flops / win["seconds"] / 1e9}
