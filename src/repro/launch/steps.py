"""Jit-able step functions (train / prefill / decode) + input specs.

These are the exact functions the dry-run lowers at 256/512 devices and the
train/serve loops execute for real; one definition, both uses.

Sparse-FFN archs need no special handling here: the structure metadata the
SpMM dispatch keys on is STATIC aux data re-derived inside ``mlp()`` from
the arch config (``models.layers.mlp_sparse_metas``), so every step traced
from these functions — train, prefill, decode — resolves the same real
per-shard kernel picks as the raw ``dist_spmm`` API, with no extra
arguments threaded through params or inputs.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import transformer as T
from repro.optim import adamw


# --------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (weak-type-correct, shardable, no device allocation)."""
    sds = jax.ShapeDtypeStruct
    B, L = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        if cfg.input_mode == "codebooks":
            batch = {"tokens": sds((B, L, cfg.n_codebooks), jnp.int32),
                     "labels": sds((B, L, cfg.n_codebooks), jnp.int32)}
        elif cfg.input_mode == "tokens+patches":
            lt = L - cfg.patch_tokens
            batch = {"tokens": sds((B, lt), jnp.int32),
                     "patch_embeds": sds((B, cfg.patch_tokens, cfg.d_model),
                                         jnp.bfloat16),
                     "labels": sds((B, lt), jnp.int32)}
        else:
            batch = {"tokens": sds((B, L), jnp.int32),
                     "labels": sds((B, L), jnp.int32)}
        if shape.kind == "prefill":
            batch.pop("labels")
        return batch
    # decode: one new token against a cache of length L
    if cfg.input_mode == "codebooks":
        return {"tokens": sds((B, cfg.n_codebooks), jnp.int32)}
    return {"tokens": sds((B,), jnp.int32)}


# ------------------------------------------------------------------- steps
def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    remat: str = "full"):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, parts = T.train_loss(cfg, p, batch, remat=remat)
            return loss, parts
        (loss, parts), grads = jax.value_and_grad(
            loss_fn, has_aux=True, allow_int=True)(params)
        params2, opt_state2, om = adamw.update(opt_cfg, grads, opt_state,
                                               params)
        metrics = {"loss": loss, **parts, **om}
        return params2, opt_state2, metrics
    return train_step


def make_prefill_step(cfg: ModelConfig, cache_len: int, stats: bool = False,
                      capture: bool = False):
    """``(params, batch) -> (last-position logits, cache)``: what serving
    samples from; the head runs at the last position only.  ``stats`` adds
    the MoE layers' routing stats third (``models.moe.moe_layer``), and
    ``capture`` each MoE layer's routed input, gate weights and routed
    output to them, for checking the routed experts alone."""
    def prefill_step(params, batch):
        return T.prefill(cfg, params, batch, cache_len,
                         stats=stats or capture, capture=capture)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens, pos):
        return T.decode_step(cfg, params, cache, tokens, pos)
    return serve_step


def opt_specs(cfg: ModelConfig, params_specs):
    return jax.eval_shape(adamw.init, params_specs)
