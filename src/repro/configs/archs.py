"""The 10 assigned architectures (exact configs per the assignment) plus the
paper's own ``smat-ffn`` arch (block-sparse FFN LM — the SpMM technique as a
first-class training feature).

Sources noted inline; dimensions follow the assignment block verbatim.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, YarnRope
from repro.core.attention_mask import AttnSparsitySpec, banded
from repro.core.sparse_linear import SparsitySpec


ARCHS = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    ARCHS[cfg.name] = cfg
    return cfg


# --------------------------------------------------------------------- [ssm]
# SSD (state-space duality), arXiv:2405.21060
_register(ModelConfig(
    name="mamba2-1.3b", family="ssm", layout="ssd",
    n_layers=48, d_model=2048, n_heads=0, n_kv_heads=0, head_dim=0,
    d_ff=0, vocab_size=50280,
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_groups=1,
))

# --------------------------------------------------------------------- [moe]
# DeepSeek-V2(-Lite), arXiv:2405.04434 — MLA kv_lora=512, shared+routed top-6
# The published config.json: one leading dense layer (10,944 wide), then 26
# MoE layers of 64 routed experts (1,408 wide, top-6 by softmax, weights not
# renormalised) and 2 shared; YaRN RoPE.  Served dropless on the SMaT
# kernels (``moe_dispatch="bcsr"``).
_register(ModelConfig(
    name="deepseek-v2-lite-16b", family="moe", layout="mla_moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944, vocab_size=102400, rope_theta=10_000.0,
    rope_scaling=YarnRope(factor=40.0, original_max_position_embeddings=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
    use_mla=True, kv_lora_rank=512, q_lora_rank=None,
    rope_head_dim=64, nope_head_dim=128, v_head_dim=128,
    n_experts=64, n_shared_experts=2, moe_top_k=6, expert_d_ff=1408,
    first_k_dense_replace=1, norm_topk_prob=False, routed_scaling_factor=1.0,
    moe_dispatch="bcsr",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
    assumed=("rope dims in the rotate-half layout: the published "
             "interleaved layout is a fixed permutation of the wq / wkv_a "
             "rope columns",
             "norms scale by (1 + w) with w initialised to 0 (the published "
             "w initialised to 1)"),
))

_register(ModelConfig(
    name="deepseek-v2-236b", family="moe", layout="mla_moe",
    n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=1536, vocab_size=102400,
    use_mla=True, kv_lora_rank=512, q_lora_rank=1536,
    rope_head_dim=64, nope_head_dim=128, v_head_dim=128,
    n_experts=160, n_shared_experts=2, moe_top_k=6, expert_d_ff=1536,
))

# --------------------------------------------------------------------- [vlm]
# Pixtral-12B: pixtral-ViT (STUB frontend) + mistral-nemo backbone
_register(ModelConfig(
    name="pixtral-12b", family="vlm", layout="attn_mlp",
    n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=14336, vocab_size=131072, rope_theta=1_000_000.0,
    input_mode="tokens+patches", patch_tokens=1024,
))

# ------------------------------------------------------------------- [dense]
# H2O-Danube-1.8B, arXiv:2401.16818 — llama+mistral mix, sliding window
_register(ModelConfig(
    name="h2o-danube-1.8b", family="dense", layout="attn_mlp",
    n_layers=24, d_model=2560, n_heads=32, n_kv_heads=8, head_dim=80,
    d_ff=6912, vocab_size=32000, sliding_window=4096,
))

# Minitron-4B (pruned Nemotron), arXiv:2407.14679
_register(ModelConfig(
    name="minitron-4b", family="dense", layout="attn_mlp",
    n_layers=32, d_model=3072, n_heads=24, n_kv_heads=8, head_dim=128,
    d_ff=9216, vocab_size=256000,
))

# Qwen2.5-14B — GQA + QKV bias
_register(ModelConfig(
    name="qwen2.5-14b", family="dense", layout="attn_mlp",
    n_layers=48, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
    d_ff=13824, vocab_size=152064, qkv_bias=True, rope_theta=1_000_000.0,
))

# Gemma2-27B, arXiv:2408.00118 — local/global alternation, logit softcaps
_register(ModelConfig(
    name="gemma2-27b", family="dense", layout="gemma_pair",
    n_layers=46, d_model=4608, n_heads=32, n_kv_heads=16, head_dim=128,
    d_ff=36864, vocab_size=256000, sliding_window=4096,
    attn_logit_softcap=50.0, final_logit_softcap=30.0, mlp_act="gelu",
))

# ------------------------------------------------------------------ [hybrid]
# Zamba2-7B, arXiv:2411.15242 — Mamba2 backbone + shared attention block
_register(ModelConfig(
    name="zamba2-7b", family="hybrid", layout="zamba",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=112,
    d_ff=14336, vocab_size=32000,
    ssm_state=64, ssm_expand=2, ssm_head_dim=64, ssm_groups=1,
    hybrid_unit_len=5, hybrid_n_units=13, hybrid_tail=3,
))

# ------------------------------------------------------------------- [audio]
# MusicGen-medium, arXiv:2306.05284 — decoder over EnCodec tokens (stub
# frontend: 4 codebooks, vocab 2048 each)
_register(ModelConfig(
    name="musicgen-medium", family="audio", layout="attn_mlp",
    n_layers=48, d_model=1536, n_heads=24, n_kv_heads=24, head_dim=64,
    d_ff=6144, vocab_size=2048,
    input_mode="codebooks", n_codebooks=4,
))

# --------------------------------------------------- the paper's own arch
# LM whose FFN weights are 90% block-sparse, multiplied by the SMaT kernels.
_register(ModelConfig(
    name="smat-ffn-1.3b", family="dense", layout="attn_mlp",
    n_layers=24, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=8192, vocab_size=32000,
    ffn_sparsity=SparsitySpec(density=0.10, block=(128, 128), backend="auto"),
))

# Both sparse workloads at once: block-sparse FFN weights AND block-sparse
# attention scores (banded mask, SDDMM -> block softmax -> SpMM).  The
# banded mask bounds the attended window, so this arch qualifies for the
# 500k decode cell like the SWA archs do.
_register(ModelConfig(
    name="smat-attn-1.3b", family="dense", layout="attn_mlp",
    n_layers=24, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=8192, vocab_size=32000,
    ffn_sparsity=SparsitySpec(density=0.10, block=(128, 128), backend="auto"),
    attn_sparsity=AttnSparsitySpec(mask=banded(4096), block=(128, 128),
                                   backend="auto"),
))


# ---------------------------------------------------------------- smoke view
def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests: few layers, small
    width, tiny vocab; one forward/train step must run and be NaN-free.
    Sparse specs run the ``xla`` backend here: the smoke view is the CPU
    test path, where no Pallas kernel is compiled."""
    kw = dict(
        name=cfg.name + ":smoke",
        n_layers=2 if cfg.layout != "gemma_pair" else 2,
        d_model=128,
        vocab_size=512,
        d_ff=256 if cfg.d_ff else 0,
    )
    if cfg.n_heads:
        kw.update(n_heads=4, n_kv_heads=min(cfg.n_kv_heads, 2) or 2,
                  head_dim=32)
    if cfg.use_mla:
        kw.update(kv_lora_rank=64,
                  q_lora_rank=64 if cfg.q_lora_rank else None,
                  rope_head_dim=16, nope_head_dim=32, v_head_dim=32)
    if cfg.n_experts:
        kw.update(n_experts=4, n_shared_experts=min(cfg.n_shared_experts, 1),
                  moe_top_k=2, expert_d_ff=64, moe_backend="xla")
    if cfg.first_k_dense_replace:
        kw.update(first_k_dense_replace=1)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
    if cfg.layout == "zamba":
        kw.update(hybrid_unit_len=2, hybrid_n_units=2, hybrid_tail=1,
                  n_layers=5)
    if cfg.sliding_window:
        kw.update(sliding_window=64)
    if cfg.patch_tokens:
        kw.update(patch_tokens=8)
    if cfg.ffn_sparsity is not None:
        kw.update(ffn_sparsity=SparsitySpec(
            density=0.3, block=(16, 16), backend="xla",
            bn=128, interpret=True))
    if cfg.attn_sparsity is not None:
        kw.update(attn_sparsity=dataclasses.replace(
            cfg.attn_sparsity, mask=banded(32), block=(16, 16),
            backend="xla", bn=128, interpret=True))
    return dataclasses.replace(cfg, **kw)


def xla_lowered(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` with every sparse spec on the ``xla`` backend: the CPU
    dry-run's stand-in for the chip's Pallas kernels, and the reference
    a chip run compares its kernels against."""
    kw = {}
    for field in ("ffn_sparsity", "attn_sparsity"):
        spec = getattr(cfg, field)
        if spec is not None and spec.backend != "xla":
            kw[field] = dataclasses.replace(spec, backend="xla")
    if cfg.moe_dispatch == "bcsr" and cfg.moe_backend != "xla":
        kw["moe_backend"] = "xla"
    return dataclasses.replace(cfg, **kw) if kw else cfg
