"""Compile the main path's kernels and the full-width decode step for one
TPU v5e chip, with no chip attached.

The chip is described (``jax.experimental.topologies``), not present: the
TPU compiler refuses here what it would refuse on the chip — unaligned
tiles, kernels over the VMEM budget, programs that do not fit — at no chip
time.  Nothing runs, so these tests say nothing about results or speed.
The topology is described inside a module-scoped fixture (never while a
module is imported), and all compiles live in this one file: the worker
that takes it is the only one that loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bcsr_attn
from repro.kernels import bcsr_spmm as pk

BLOCK = (128, 128)
M, K, N = 8192, 2048, 512                 # smat FFN width, wide token panel
NNZB = 103                                 # 10% of the 64 x 16 block grid
ATTN_LEN, ATTN_BAND, HEADS, HEAD_DIM = 32_768, 4096, 16, 128
# HPCG 64^3 in 16 x 128 blocks: 262,144 rows, 95,760 stored blocks
HPCG_BLOCK, HPCG_ROWS, HPCG_NNZB = (16, 128), 262_144, 95_760
# DeepSeek-V2-Lite's gate/up SDDMM over 8 x 4,096 tokens: 1,600 block-rows
# of 128 sorted token rows (top-6 of 64 experts), 11 blocks of 128 columns
# of the experts' [64 * 1,408, 2,048] weights each
MOE_ROWS, MOE_COLS, MOE_D, MOE_NNZB = 1_600 * 128, 64 * 1_408, 2_048, 17_600


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases(s):
    nbr, nbc = M // BLOCK[0], K // BLOCK[1]
    i32, bf16 = jnp.int32, jnp.bfloat16
    ids = _sds(s, (NNZB,), i32)
    vals = _sds(s, (NNZB,) + BLOCK, bf16)
    hpcg_ids = _sds(s, (HPCG_NNZB,), i32)
    hpcg_vals = _sds(s, (HPCG_NNZB,) + HPCG_BLOCK, bf16)
    hpcg_nbr = HPCG_ROWS // HPCG_BLOCK[0]
    return {
        **{f"spmm_nnz_stream_hpcg_bn{bn}": (
            functools.partial(pk.bcsr_spmm_nnz_stream,
                              n_block_rows=hpcg_nbr, bn=bn),
            (hpcg_vals, hpcg_ids, hpcg_ids, _sds(s, (HPCG_ROWS, bn), bf16)))
           for bn in (128, 512)},
        "spmm_nnz_stream": (
            functools.partial(pk.bcsr_spmm_nnz_stream, n_block_rows=nbr,
                              bn=N),
            (vals, ids, ids, _sds(s, (K, N), bf16))),
        "sddmm": (
            functools.partial(pk.bcsr_sddmm, h=BLOCK[0], w=BLOCK[1]),
            (_sds(s, (M, N), bf16), _sds(s, (K, N), bf16), ids, ids)),
        "sddmm_hpcg_n512": (
            functools.partial(pk.bcsr_sddmm, h=HPCG_BLOCK[0],
                              w=HPCG_BLOCK[1]),
            (_sds(s, (HPCG_ROWS, 512), bf16), _sds(s, (HPCG_ROWS, 512), bf16),
             hpcg_ids, hpcg_ids)),
        "sddmm_moe_d2048": (
            functools.partial(pk.bcsr_sddmm, h=BLOCK[0], w=BLOCK[1]),
            (_sds(s, (MOE_ROWS, MOE_D), bf16), _sds(s, (MOE_COLS, MOE_D), bf16),
             _sds(s, (MOE_NNZB,), i32), _sds(s, (MOE_NNZB,), i32))),
        # a contraction of 32,768 (the SpMM backward's dW over a long token
        # axis): the panel's X does not fit whole, 8 chunks of 4,096
        "sddmm_chunked_n32768": (
            functools.partial(pk.bcsr_sddmm, h=BLOCK[0], w=BLOCK[1]),
            (_sds(s, (M, 32_768), bf16), _sds(s, (K, 32_768), bf16),
             ids, ids)),
    }


@pytest.mark.parametrize("kernel", ["spmm_nnz_stream", "sddmm",
                                    "spmm_nnz_stream_hpcg_bn128",
                                    "spmm_nnz_stream_hpcg_bn512",
                                    "sddmm_hpcg_n512", "sddmm_moe_d2048",
                                    "sddmm_chunked_n32768"])
def test_bcsr_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _kernel_cases(one_chip)[kernel]
    # one launch, or one per contraction chunk of the SDDMM
    launches = 8 if kernel == "sddmm_chunked_n32768" else 1
    text = _compiled_text(fn, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == launches


def test_fused_attention_compiles_for_v5e_at_32k(one_chip):
    # banded(4096) on 128 x 128 blocks: block-row i stores blocks
    # max(0, i - 32) .. i
    h, w = BLOCK
    nbr = ATTN_LEN // h
    per_row = ATTN_BAND // w + 1
    nnzb = sum(min(i + 1, per_row) for i in range(nbr))
    s = one_chip
    qkv = _sds(s, (HEADS, ATTN_LEN, HEAD_DIM), jnp.float32)
    sched = _sds(s, (nbr * per_row,), jnp.int32)
    fn = functools.partial(
        bcsr_attn.bcsr_attn_fused, n_block_rows=nbr, n_block_cols=nbr,
        block=BLOCK, scale=HEAD_DIM ** -0.5, out_dtype=jnp.float32)
    text = _compiled_text(fn, qkv, qkv, qkv,
                          _sds(s, (nnzb,) + BLOCK, jnp.float32), sched, sched)
    assert "tpu_custom_call" in text


def test_smat_attn_decode_step_runs_pallas_kernels(one_chip):
    """The registered smat-attn-1.3b (backend="auto") decode step at full
    width, 4 slots x 8192 cache: its sparse FFN layers must compile to
    Pallas kernels, not to the xla gather path."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("smat-attn-1.3b")

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(T.param_specs(cfg))
    cache = on_chip(T.cache_specs(cfg, 4, 8192))
    text = _compiled_text(
        functools.partial(T.decode_step, cfg), params, cache,
        _sds(one_chip, (4,), jnp.int32), _sds(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


def test_deepseek_moe_layer_runs_pallas_kernels(one_chip):
    """One dropless ``bcsr`` expert layer of the registered
    deepseek-v2-lite-16b at its published widths (64 experts of 1,408,
    hidden 2,048, top-6) over 4,096 tokens: ``auto`` on the device-built
    structure resolves to the Pallas SDDMM (gate, up) and SpMM (down)."""
    from repro.configs import get_config
    from repro.models import moe as M
    cfg = get_config("deepseek-v2-lite-16b")
    p = jax.eval_shape(lambda: M.init_moe(cfg, jax.random.PRNGKey(0),
                                          jnp.bfloat16))
    p = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), p)
    x = _sds(one_chip, (1, 4096, cfg.d_model), jnp.bfloat16)
    text = _compiled_text(
        lambda p, x: M.moe_layer(cfg, p, x, dispatch="bcsr")[0], p, x)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 3
