"""Static attention-mask specs for block-sparse attention.

Pure host-side dataclasses + the element-level predicate — the LEAF layer
of the attention subsystem, importable from anywhere (``repro.configs``
declares arch defaults with these; ``repro.models.attention`` builds the
BCSR pipeline and the actual SDDMM/softmax/SpMM layer on top and
re-exports everything here, so ``from repro.models import attention as A;
A.banded(...)`` remains the user-facing spelling).

Keeping the specs below ``configs`` preserves the one-directional layer
map (``docs/ARCHITECTURE.md``): core imports nothing above it, configs
imports core, models imports everything.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnMaskSpec:
    """Static element-level attention mask pattern (hashable).

    ``kind`` picks the predicate; every kind is causal.  ``window_cap``
    intersects an additional sliding-window bound (used when a config
    combines ``sliding_window`` with sparse attention).  Build instances
    with the ``banded`` / ``local_global`` / ``blockwise_causal``
    constructors below.
    """
    kind: str                     # banded | local_global | blockwise_causal
    bandwidth: int = 0            # banded: k > q - bandwidth
    window: int = 0               # local_global: local window
    n_global: int = 0             # local_global: always-visible prefix keys
    window_cap: int = 0           # optional extra sliding-window intersect


def banded(bandwidth: int) -> AttnMaskSpec:
    """Sliding-window (banded) causal mask: query q sees keys
    ``(q - bandwidth, q]``.

    >>> from repro.models import attention as A
    >>> spec = A.banded(32)
    >>> meta = A.attention_mask_meta(spec, seq_len=128, block=(16, 16))
    >>> (meta.shape, meta.nnzb > 0, meta.max_bpr)
    ((128, 128), True, 3)
    """
    if bandwidth < 1:
        raise ValueError(f"bandwidth must be >= 1, got {bandwidth}")
    return AttnMaskSpec(kind="banded", bandwidth=bandwidth)


def local_global(window: int, n_global: int) -> AttnMaskSpec:
    """Local sliding window + a globally visible key prefix (the
    longformer/big-bird shape): query q sees keys ``(q - window, q]`` and
    keys ``< n_global``.

    >>> from repro.models import attention as A
    >>> m_lg = A.attention_mask_meta(A.local_global(32, 16), 128, (16, 16))
    >>> m_b = A.attention_mask_meta(A.banded(32), 128, (16, 16))
    >>> m_lg.nnzb > m_b.nnzb        # the global column strip adds blocks
    True
    """
    if window < 1 or n_global < 0:
        raise ValueError(f"bad local_global({window}, {n_global})")
    return AttnMaskSpec(kind="local_global", window=window,
                        n_global=n_global)


def blockwise_causal() -> AttnMaskSpec:
    """Plain causal attention realized blockwise — every block on or below
    the block diagonal is stored, the diagonal blocks mask element-causally
    inside.  Numerically identical to dense causal attention (the oracle
    the tests pin), at dense-causal cost: use it as the correctness anchor,
    the banded/local_global specs for actual sparsity wins."""
    return AttnMaskSpec(kind="blockwise_causal")


def mask_allowed(spec: AttnMaskSpec, q_pos, k_pos):
    """Element-level predicate ``[..., Lq, Sk]`` — works on numpy (host
    mask construction) and jnp (decode-step bias) index arrays alike."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    ok = (k <= q) & (k >= 0)
    if spec.kind == "banded":
        ok = ok & (k > q - spec.bandwidth)
    elif spec.kind == "local_global":
        ok = ok & ((k > q - spec.window) | (k < spec.n_global))
    elif spec.kind != "blockwise_causal":
        raise ValueError(f"unknown mask kind {spec.kind!r}")
    if spec.window_cap:
        ok = ok & (k > q - spec.window_cap)
    return ok


@dataclasses.dataclass(frozen=True)
class AttnSparsitySpec:
    """Config for block-sparse attention (the second workload toggle —
    ``ModelConfig.attn_sparsity``).

    ``mask`` is the static pattern; ``block`` the BCSR tile of the score
    matrix (lane/sublane-aligned on real TPUs, anything in interpret
    mode).  ``backend`` feeds BOTH ops — with ``"auto"`` the attention
    layer first arbitrates fused-vs-composed through the ``op="attn"``
    family, then (composed) the SDDMM and the SpMM resolve independently
    from their own v6 fingerprint families; ``"fused"`` forces the
    single-launch ``kernels.bcsr_attn`` path (bit-for-bit equal forward,
    composed backward).  ``shards > 0`` row-partitions the score
    structure through ``launch.dist_spmm`` for the context product
    (shard_map under a compatible ambient mesh from
    ``dist_spmm.use_spmm_mesh``, identical in-process math otherwise) —
    sharded specs always run composed.

    ``paged_decode`` controls the serving decode path (PR 8): ``"auto"``
    gathers KV through the mask-BCSR page table whenever that touches
    strictly fewer pages than the cache holds (banded / local_global
    masks), ``"force"`` takes the paged path whenever it is structurally
    possible (cache_len divisible by the mask block width), ``"off"``
    keeps the dense-bias decode.  All three are bitwise-identical to the
    full-table run of the same machinery — the paged gather only skips
    pages whose softmax contribution is exactly zero."""
    mask: AttnMaskSpec = dataclasses.field(default_factory=blockwise_causal)
    block: Tuple[int, int] = (16, 16)
    backend: str = "auto"   # pallas | xla | dense | auto | fused
    bn: int = 512
    interpret: bool = False
    shards: int = 0                 # >0: row-shard the score structure
    paged_decode: str = "auto"      # auto | force | off (serving decode)
