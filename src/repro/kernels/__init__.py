"""SpMM/SDDMM kernel package.

  bcsr_spmm — Pallas TPU kernels (nnz_stream SpMM / streamed sddmm)
  bcsr_attn — fused one-kernel block-sparse attention (flash-style
              single launch over the static schedule; bit-for-bit equal
              to the composed SDDMM -> softmax -> SpMM triple in f32)
  ref       — pure-jnp oracles (the ``xla`` backend, dense-masked sddmm)
  ops       — jit-ready public API (``spmm`` + ``sddmm``, mutually-dual
              custom VJPs + dispatch)
  autotune  — kernel-variant registry (spmm + sddmm + attn families) +
              fingerprint-cached autotuner (v6 ``op=``-scoped keys;
              ``backend="auto"`` routes through it)
"""
from repro.kernels import ops
from repro.kernels.bcsr_attn import bcsr_attn_fused
from repro.kernels.ops import prepare_sparse, sddmm, spmm
