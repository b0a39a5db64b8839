"""Useful operations and bytes of a model step, from the configuration's
published sizes alone, and of the SDDMM op from its problem's shapes.

As ``chipbench/counts.py`` does for the library op, these are the
yardstick's own counts: padding rows, capacity blocks, recomputation and
the program's layouts are not counted, so a change to any of them is
judged by the same count.
"""
from __future__ import annotations


def _sizes(c: dict):
    return (c["hidden_size"], c["num_attention_heads"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["kv_lora_rank"])


def mla_projection_flops(c: dict) -> int:
    """Per token and layer: the q, kv-down, kv-up and output projections
    (2 operations per multiply-add)."""
    d, h, nope, rope, vd, r = _sizes(c)
    q_dim = h * (nope + rope)
    if c.get("q_lora_rank"):
        q = d * c["q_lora_rank"] + c["q_lora_rank"] * q_dim
    else:
        q = d * q_dim
    return 2 * (q + d * (r + rope) + r * h * (nope + vd) + h * vd * d)


def attention_flops(c: dict, seq_len: int) -> int:
    """Per sequence and layer: causal attention at its true positions,
    QK over ``nope + rope`` dims and PV over ``v_head_dim``; the query at
    position p attends p + 1 keys."""
    _, h, nope, rope, vd, _ = _sizes(c)
    keys = seq_len * (seq_len + 1) // 2
    return 2 * h * keys * (nope + rope + vd)


def expert_flops(c: dict) -> int:
    """Per (token, expert) pair: gate, up and down of one routed expert."""
    return 2 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def prefill_flops(c: dict, batch: int, seq_len: int) -> int:
    """Useful operations of one prefill step of ``batch`` sequences of
    ``seq_len`` tokens through the configuration's layers: per token the
    MLA projections, the dense FFN of the leading layers, and in each MoE
    layer the router, the shared experts and the top-k routed experts;
    per sequence the causal attention and the head at the last position
    only."""
    d = c["hidden_size"]
    n = c["num_hidden_layers"]
    k_dense = min(c["first_k_dense_replace"], n)
    per_token = n * mla_projection_flops(c)
    per_token += k_dense * 2 * 3 * d * c["intermediate_size"]
    moe = 2 * d * c["n_routed_experts"]                          # router
    moe += c["n_shared_experts"] * expert_flops(c)
    moe += c["num_experts_per_tok"] * expert_flops(c)
    per_token += (n - k_dense) * moe
    per_seq = n * attention_flops(c, seq_len) + 2 * d * c["vocab_size"]
    return batch * (seq_len * per_token + per_seq)


def moe_expert_counts(c: dict, tokens: int, act_bytes: int = 2,
                      weight_bytes: int = 2) -> dict:
    """``{"ops", "bytes"}`` of the routed experts of every MoE layer of
    one step over ``tokens`` tokens: ``2 * 3 * D * F`` operations per
    routed (token, expert) pair; bytes the experts' weights read once,
    and each routed row read in and written out once."""
    n_moe = c["num_hidden_layers"] - min(c["first_k_dense_replace"],
                                         c["num_hidden_layers"])
    d, f, e = (c["hidden_size"], c["moe_intermediate_size"],
               c["n_routed_experts"])
    pairs = tokens * c["num_experts_per_tok"]
    ops = pairs * expert_flops(c)
    nbytes = 3 * e * d * f * weight_bytes + 2 * pairs * d * act_bytes
    return {"ops": n_moe * ops, "bytes": n_moe * nbytes}


def sddmm_counts(*, nnz: int, n_rows: int, n_cols: int, n: int,
                 io_bytes: int, out_bytes: int):
    """``(operations, bytes)`` of ``vals = (X @ Y^T)`` sampled at ``nnz``
    nonzeros, ``X [n_rows, n]``, ``Y [n_cols, n]``: ``2 * nnz * n``
    operations; X and Y read once, each nonzero's value written once with
    its 4-byte index."""
    ops = 2 * nnz * n
    nbytes = (n_rows + n_cols) * n * io_bytes + nnz * (out_bytes + 4)
    return ops, nbytes
