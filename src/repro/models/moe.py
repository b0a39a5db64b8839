"""Mixture-of-Experts FFN (DeepSeek-V2 style: shared + routed top-k).

Three dispatch implementations:

  * ``gather`` (default) — sort/scatter-based capacity dispatch computed PER
    BATCH ROW (capacity C = cf * L * k / E per row).  Token movement is
    gathers/scatters (zero matmul FLOPs); expert FFN is the only dense
    compute.  Shards cleanly: rows over `data`, experts over `model` (EP) —
    the cross-shard token exchange lowers to the all-to-all-class collective
    a real EP implementation performs.

  * ``einsum``  — the classic GShard one-hot dispatch-einsum formulation.
    Kept as a benchmark arm: its dispatch tensors/FLOPs are the well-known
    scaling trap (see EXPERIMENTS.md §Perf for the measured difference).

  * ``bcsr``    — dropless, in the MegaBlocks form (arXiv:2211.15841): the
    (token, slot) pairs of the whole batch are sorted by expert, each
    expert's group is padded to whole 128-row blocks (``expert_block``),
    and the routed products are block-sparse products on the SMaT kernels
    — gate and up as ``ops.sddmm`` of the sorted tokens against the
    experts' stacked columns, down as ``ops.spmm``.  The structure (which
    expert each block-row belongs to) is built on the device every step,
    with a static block capacity (``expert_capacity``); no token is ever
    dropped.  Its expert weights are stored ``[E * F, D]`` (``init_moe``).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from repro.launch.constrain import BATCH, MODEL, constrain
from repro.models.layers import _init, mlp


def init_moe(cfg, key, dtype):
    """Router, routed experts and the fused shared experts.  The routed
    weights are ``[E, D, F]`` / ``[E, F, D]``, or under ``bcsr`` dispatch
    all three ``[E * F, D]``: row ``e * F + j`` is expert ``e``'s gate
    (up) column ``j``, or its down row ``j``."""
    d, f = cfg.d_model, cfg.expert_d_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    if cfg.moe_dispatch == "bcsr":
        shapes = ((e * f, d),) * 3
    else:
        shapes = ((e, d, f), (e, d, f), (e, f, d))
    p = {
        "router": _init(ks[0], (d, e), s, jnp.float32),
        "w_gate": _init(ks[1], shapes[0], s, dtype),
        "w_up": _init(ks[2], shapes[1], s, dtype),
        "w_down": _init(ks[3], shapes[2], f ** -0.5, dtype),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _init(kss[0], (d, fs), s, dtype),
            "w_up": _init(kss[1], (d, fs), s, dtype),
            "w_down": _init(kss[2], (fs, d), fs ** -0.5, dtype),
        }
    return p


def _route(cfg, p, xt):
    """xt [..., T, D] -> (gate_vals, gate_idx, aux)."""
    e, k = cfg.n_experts, cfg.moe_top_k
    logits = jnp.einsum("...td,de->...te", xt.astype(jnp.float32),
                        p["router"], precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate_vals = gate_vals * cfg.routed_scaling_factor
    # Switch-style load-balance loss
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))
    onehot_mean = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
        1.0 / gate_idx.size)
    aux = e * jnp.sum(me * onehot_mean)
    return gate_vals, gate_idx, aux


def _expert_stack(cfg, p):
    """The routed weights as ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``,
    whichever layout ``init_moe`` stored them in."""
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if wg.ndim == 2:                                   # bcsr: [E * F, D]
        e, f, d = cfg.n_experts, cfg.expert_d_ff, cfg.d_model
        wg = wg.reshape(e, f, d).transpose(0, 2, 1)
        wu = wu.reshape(e, f, d).transpose(0, 2, 1)
        wd = wd.reshape(e, f, d)
    return wg, wu, wd


def _expert_mlp(cfg, p, xin):
    """xin [..., E, C, D] -> [..., E, C, D]"""
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    wg, wu, wd = _expert_stack(cfg, p)
    h = act(jnp.einsum("...ecd,edf->...ecf", xin, wg)) * \
        jnp.einsum("...ecd,edf->...ecf", xin, wu)
    return jnp.einsum("...ecf,efd->...ecd", h, wd)


# ------------------------------------------------------------ gather dispatch
def _dispatch_row(e_flat):
    """Per-row slot assignment.  e_flat [Lk] = expert of each (token,slot);
    returns pos [Lk]: position within that expert's queue (stable order)."""
    Lk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    r = jnp.arange(Lk, dtype=jnp.int32)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_sorted = (r - first).astype(jnp.int32)
    return jnp.zeros((Lk,), jnp.int32).at[order].set(pos_sorted)


def _moe_gather(cfg, p, x):
    """x [B, L, D]; per-row capacity; gather/scatter token movement."""
    B, L, D = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    C = int(cfg.capacity_factor * L * k / e) + 1

    gate_vals, gate_idx, aux = _route(cfg, p, x)          # [B,L,k]
    e_flat = gate_idx.reshape(B, L * k)
    pos = jax.vmap(_dispatch_row)(e_flat)                 # [B, Lk]
    keep = pos < C
    tok = jnp.tile(jnp.arange(L, dtype=jnp.int32)[:, None],
                   (1, k)).reshape(L * k)

    # scatter token index / gate weight into the [E, C] slot tables; dropped
    # entries get an out-of-bounds expert id, discarded by mode="drop"
    gates_flat = gate_vals.reshape(B, L * k)

    def scatter_row(ef, ps, kp, gv):
        ii = (jnp.where(kp, ef, e), jnp.where(kp, ps, 0))
        st = jnp.full((e, C), L, jnp.int32).at[ii].set(tok, mode="drop")
        sw = jnp.zeros((e, C), jnp.float32).at[ii].set(gv, mode="drop")
        return st, sw
    slot_tok, slot_w = jax.vmap(scatter_row)(e_flat, pos, keep, gates_flat)

    x_pad = jnp.concatenate(
        [x, jnp.zeros((B, 1, D), x.dtype)], axis=1)       # pad row L -> zeros
    xin = jax.vmap(lambda xp, st: xp[st])(x_pad, slot_tok)  # [B, E, C, D]
    xin = constrain(xin, BATCH, MODEL)                     # rows x EP

    eout = _expert_mlp(cfg, p, xin)                        # [B, E, C, D]
    eout = constrain(eout, BATCH, MODEL)
    eout = _checkpoint_name(eout, "moe_eout")

    # combine: scatter-add each slot's gate-weighted output back to its
    # token (expert-sharded partial sums -> one psum of [B, L, D] — §Perf B1;
    # the gather-based combine all-gathered the full [B, E, C, D] instead)
    contrib = eout * slot_w[..., None].astype(eout.dtype)  # [B, E, C, D]

    def combine_row(st, cb):
        y = jnp.zeros((L + 1, D), cb.dtype)
        return y.at[st.reshape(e * C)].add(cb.reshape(e * C, D))
    y = jax.vmap(combine_row)(slot_tok, contrib)[:, :L]
    y = constrain(y, BATCH)
    return y.astype(x.dtype), aux


# ------------------------------------------------------------ einsum dispatch
def _moe_einsum(cfg, p, x):
    """GShard one-hot dispatch (benchmark arm)."""
    B, L, D = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    C = int(cfg.capacity_factor * L * k / e) + 1
    gate_vals, gate_idx, aux = _route(cfg, p, x)

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)   # [B,L,k,E]
    flat = onehot.reshape(B, L * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(B, L, k, e)
    pos = jnp.sum(pos * onehot, axis=-1)                      # [B,L,k]
    keep = pos < C
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * \
        keep[..., None].astype(jnp.float32)
    dispatch = jnp.einsum("blke,blkc->blec", onehot, pos_oh)
    combine = jnp.einsum("blke,blkc,blk->blec", onehot, pos_oh,
                         gate_vals.astype(jnp.float32))
    xin = jnp.einsum("blec,bld->becd", dispatch.astype(x.dtype), x)
    eout = _expert_mlp(cfg, p, xin)
    y = jnp.einsum("blec,becd->bld", combine.astype(x.dtype), eout)
    return y, aux


# -------------------------------------------------------------- bcsr dispatch
BLOCK_ROWS = 128        # token rows of a block: one MXU pass, one lane tile


def expert_block(d_ff: int):
    """``(token rows, expert columns)`` of a block of the routed products:
    128 rows, and the largest divisor of the expert width up to 128 (128
    at DeepSeek-V2-Lite's 1,408)."""
    return BLOCK_ROWS, math.gcd(d_ff, 128)


def expert_capacity(n_pairs: int, n_experts: int, block_rows: int) -> int:
    """Static block-rows enough for any routing of ``n_pairs`` (token,
    slot) pairs: each expert's group pads to whole blocks, so at most
    ``ceil(n_pairs / block_rows)`` full blocks plus one partial block for
    each expert that receives a pair."""
    return -(-n_pairs // block_rows) + min(n_experts, n_pairs)


def expert_rows(gate_idx, n_experts: int, block_rows: int):
    """Sort the (token, slot) pairs by expert and pad each expert's group
    to whole blocks of ``block_rows`` rows.

    ``gate_idx`` [T, k] -> dict of device arrays:

    * ``row_of_pair`` [T * k]: the pair's row in the sorted, padded layout
      (pairs of one expert keep token order);
    * ``expert_of_block`` [R_cap]: the expert each block-row belongs to;
      the capacity block-rows past the last used one repeat expert E - 1;
    * ``real`` [R_cap] bool: the block-row holds a routed pair;
    * ``counts`` [E]: pairs routed to each expert.
    """
    n = gate_idx.size
    h = block_rows
    e_flat = gate_idx.reshape(n).astype(jnp.int32)
    counts = jnp.zeros((n_experts,), jnp.int32).at[e_flat].add(1)
    blocks = (counts + h - 1) // h
    block_end = jnp.cumsum(blocks)
    block_start = block_end - blocks
    pair_start = jnp.cumsum(counts) - counts
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    rank = jnp.arange(n, dtype=jnp.int32) - pair_start[sorted_e]
    row_sorted = block_start[sorted_e] * h + rank
    row_of_pair = jnp.zeros((n,), jnp.int32).at[order].set(row_sorted)
    r_cap = expert_capacity(n, n_experts, h)
    r = jnp.arange(r_cap, dtype=jnp.int32)
    expert_of_block = jnp.minimum(
        jnp.searchsorted(block_end, r, side="right"), n_experts - 1)
    return {"row_of_pair": row_of_pair,
            "expert_of_block": expert_of_block.astype(jnp.int32),
            "real": r < block_end[-1], "counts": counts}


def expert_structure(rows, n_experts: int, d_ff: int, block):
    """The ``[R_cap * h, E * d_ff]`` block structure of the routed
    products, built on the device: block-row ``r`` holds the ``d_ff / w``
    column blocks of its expert, masked out (``real_mask``) on capacity
    block-rows.  Returns ``(SparseArrays without vals, SparseMeta)``."""
    from repro.kernels import ops
    per_row = d_ff // block[1]
    r_cap = rows["expert_of_block"].shape[0]
    row_ids = jnp.repeat(jnp.arange(r_cap, dtype=jnp.int32), per_row)
    col_ids = (rows["expert_of_block"][:, None] * per_row +
               jnp.arange(per_row, dtype=jnp.int32)[None]).reshape(-1)
    real_mask = jnp.repeat(rows["real"], per_row)
    return ops.device_sparse(row_ids, col_ids, real_mask,
                             n_block_rows=r_cap,
                             n_block_cols=n_experts * per_row, block=block,
                             max_bpr=per_row)


def _moe_bcsr(cfg, p, x, *, interpret: bool = False, capture: bool = False):
    """x [B, L, D]; dropless: every (token, slot) pair gets its expert.
    ``capture`` adds the gate weights [B, L, k] to the stats (``weights``)."""
    from repro.kernels import ops
    B, L, D = x.shape
    e, k, f = cfg.n_experts, cfg.moe_top_k, cfg.expert_d_ff
    block = expert_block(f)
    h = block[0]
    T = B * L
    xt = x.reshape(T, D)
    with jax.named_scope("smat.moe.route"):
        gate_vals, gate_idx, aux = _route(cfg, p, xt)      # [T, k]
    with jax.named_scope("smat.moe.dispatch"):
        rows = expert_rows(gate_idx, e, h)
        arrays, meta = expert_structure(rows, e, f, block)
        r_cap = meta.n_block_rows
        pair_tok = jnp.arange(T * k, dtype=jnp.int32) // k
        tok_of_row = jnp.full((r_cap * h,), T, jnp.int32).at[
            rows["row_of_pair"]].set(pair_tok)
        x_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        x_rows = x_pad[tok_of_row]                         # [R_cap * h, D]
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    kw = dict(backend=cfg.moe_backend, interpret=interpret)
    with jax.named_scope("smat.moe.experts"):
        g = ops.sddmm(arrays, meta, x_rows, p["w_gate"], **kw)
        u = ops.sddmm(arrays, meta, x_rows, p["w_up"], **kw)
        hv = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(
            x.dtype)
        y_rows = ops.spmm(arrays._replace(vals=hv), meta, p["w_down"],
                          **kw)                            # [R_cap * h, D]
    with jax.named_scope("smat.moe.combine"):
        y_pairs = y_rows[rows["row_of_pair"]].reshape(T, k, D)
        y = jnp.einsum("tkd,tk->td", y_pairs, gate_vals.astype(x.dtype),
                       preferred_element_type=jnp.float32)
    used = jnp.sum(rows["real"].astype(jnp.int32))
    stats = {"routed": jnp.asarray(T * k, jnp.int32),
             "padding": used * h - T * k,
             "capacity": (r_cap - used) * h,
             "rows_max": jnp.max(rows["counts"]),
             "experts": gate_idx.reshape(B, L, k)}
    if capture:
        stats["weights"] = gate_vals.reshape(B, L, k)
    return y.reshape(B, L, D).astype(x.dtype), aux, stats


def moe_layer(cfg, p, x, dispatch: str = "gather", *,
              interpret: bool = False, capture: bool = False):
    """x [B, L, D] -> (y [B, L, D], aux_loss scalar, stats).  ``stats`` is
    ``{}``, or under ``bcsr`` the layer's rows as int32 scalars:
    ``routed`` (T * k pairs), ``padding`` (rows that pad an expert's last
    block), ``capacity`` (rows of the unused capacity block-rows) and
    ``rows_max`` (the most pairs one expert received), and ``experts``
    [B, L, k], the experts each token was routed to.  ``capture`` (``bcsr``
    only) adds what a check of the routed experts alone needs: the layer's
    input ``routed_in`` [B, L, D], the gate weights ``weights`` [B, L, k]
    and the routed experts' output ``routed_out`` [B, L, D], before the
    shared experts are added."""
    stats = {}
    if capture and dispatch != "bcsr":
        raise ValueError("capture needs the bcsr dispatch")
    if dispatch == "bcsr":
        y, aux, stats = _moe_bcsr(cfg, p, x, interpret=interpret,
                                  capture=capture)
        if capture:
            stats = {**stats, "routed_in": x, "routed_out": y}
    elif dispatch == "gather":
        y, aux = _moe_gather(cfg, p, x)
    else:
        y, aux = _moe_einsum(cfg, p, x)
    if cfg.n_shared_experts:
        Bt, L, D = x.shape
        fs = cfg.expert_d_ff * cfg.n_shared_experts
        # shared experts are initialized DENSE (init_moe above) regardless
        # of cfg.ffn_sparsity; mlp() dispatches on the params' structure,
        # so this stays the dense einsum path even for sparse-FFN archs
        with jax.named_scope("smat.moe.shared"):
            y = y + mlp(cfg, p["shared"], x, d_ff=fs)
    return y, aux, stats


def moe_ffn(cfg, p, x, dispatch: str = "gather"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, L, D] -> (y [B, L, D], aux_loss scalar)."""
    y, aux, _ = moe_layer(cfg, p, x, dispatch)
    return y, aux


def record_stats(stats) -> None:
    """Add one step's per-layer ``stats`` (``moe_layer``'s, stacked over
    the layers, as host numbers) to the obs counters ``moe.rows{kind=
    routed|padding|capacity}`` and set the gauge ``moe.expert_rows_max``
    to the step's largest expert group."""
    from repro.obs import metrics as obs_metrics
    for kind in ("routed", "padding", "capacity"):
        obs_metrics.counter("moe.rows", kind=kind).inc(
            int(np.sum(stats[kind])))
    obs_metrics.gauge("moe.expert_rows_max").set(
        int(np.max(stats["rows_max"])))
