"""Proof that the SpMM library and the smat-attn-1.3b server run on a TPU.

    python chip_smoke.py              # one chip: library phase, server phase
    python chip_smoke.py --chips 4    # four chips: sharded SpMM only

One process, phases in order, one printed line per check.  The library
phase runs the paper's pipeline (CSR -> Jaccard reorder -> BCSR -> SpMM /
SDDMM) on SuiteSparse's mip1 at its published size, and the fused
block-sparse attention at 32k tokens; every result is compared with a
plain ``jax.numpy`` float32 reference at ``Precision.HIGHEST``.  The server
phase decodes seeded requests through ``launch.serve`` with the
full-width smat-attn-1.3b (seeded random weights) and compares one decode
step with the same step on the ``xla`` backend.  ``--chips 4`` runs only
``dist_spmm.spmm_sharded`` over a 4-chip mesh against single-device
``ops.spmm``.

Every time printed is one cold run, compilation included — not a
benchmark.  The last line of stdout is the JSON device record, printed
only when every check passed; the script exits non-zero otherwise, and
when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from typing import Callable, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, xla_lowered
from repro.core import bcsr as bcsr_lib
from repro.core import permute, topology
from repro.kernels import ops
from repro.launch import dist_spmm
from repro.launch import serve as serve_cli
from repro.launch.compile_cache import enable_compile_cache
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve.engine import ServeEngine

HIGHEST = jax.lax.Precision.HIGHEST

# Allowed max|out - ref| / max|ref| against the float32 HIGHEST reference.
# float32 operands: the kernels contract at the MXU's default precision,
# one bf16 pass per product (8-bit mantissa per operand, ~2^-9 relative).
# This limit checks bf16-product precision: it cannot tell an f32 kernel
# from a bf16 one, and it was set after the first chip run showed the
# single pass.  bfloat16 operands (the reference sees the same rounded
# values): only the bf16 output rounding, 2^-9 relative, plus f32
# accumulation order.
TOL = {"float32": 5e-3, "bfloat16": 1e-2}
# what each operand dtype's check measures, as printed
PRECISION = {"float32": "f32 operands, bf16-pass products",
             "bfloat16": "bf16 operands"}
# fused attention (f32): one-pass scores shift the softmax weights too
ATTN_TOL = 2e-2
# one bf16 decode step, Pallas kernels vs the xla backend, on the logits
LOGITS_TOL = 5e-2
# mip1 (SuiteSparse, Table I of the paper): n = 66,463, 10,352,819 nonzeros
MIP1_N, MIP1_NNZ = 66_463, 10_352_819


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at.  ``FULL`` is the chip run."""
    mip1_n: int = MIP1_N
    mip1_nnz: int = MIP1_NNZ
    block: Tuple[int, int] = (16, 128)   # bf16 sublane x lane tile
    ns: Tuple[int, ...] = (8, 512)       # paper's N, and a wide panel
    ref_rows: int = 2048                 # reference: rows densified per step
    ref_entries: int = 2048              # reference: SDDMM blocks per step
    attn_len: int = 32_768
    attn_band: int = 4096
    attn_heads: int = 16
    attn_head_dim: int = 128
    attn_block: Tuple[int, int] = (128, 128)
    attn_ref_rows: int = 512
    slots: int = 4
    cache_len: int = 8192
    requests: int = 8
    prompt_len: int = 32
    new_tokens: int = 16
    sharded_n: int = 512


FULL = Sizes()


class Checks:
    """One printed line per check; failures are collected, not raised."""

    def __init__(self):
        self.failed: List[str] = []

    def line(self, phase: str, msg: str) -> None:
        print(f"[{phase}] {msg}", flush=True)

    def check(self, phase: str, name: str, ok: bool, detail: str) -> bool:
        self.line(phase, f"{'ok  ' if ok else 'FAIL'} {name}: {detail}")
        if not ok:
            self.failed.append(f"{phase}/{name}")
        return ok

    def run(self, phase: str, fn: Callable, *args) -> None:
        """Run one phase; an exception fails the phase, not the script."""
        try:
            fn(self, *args)
        except Exception:  # reported here; the exit code carries it
            traceback.print_exc()
            self.check(phase, "phase", False, "raised (traceback above)")


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``: (compiled, seconds, n_kernels) —
    n_kernels counts the Pallas ``tpu_custom_call``s in the program."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    return compiled, secs, compiled.as_text().count("tpu_custom_call")


class ErrMax:
    """Running max|out - ref| and max|ref| over chunks."""

    def __init__(self):
        self.err = 0.0
        self.scale = 0.0

    def add(self, out, ref) -> None:
        out = jnp.asarray(out, jnp.float32)
        self.err = max(self.err, float(jnp.max(jnp.abs(out - ref))))
        self.scale = max(self.scale, float(jnp.max(jnp.abs(ref))))

    @property
    def rel(self) -> float:
        return self.err / self.scale if self.scale else float("inf")


# ----------------------------------------------------------- library phase
def mip1_csr(sz: Sizes, seed: int = 0):
    """mip1 with ``topology.SUITE``'s generator at ``sz.mip1_n`` rows.
    SUITE keeps mip1 cut ~8x per dimension for the CPU; clusters scale
    with n, which keeps SUITE's fill inside a cluster."""
    gen, kw, _ = topology.SUITE["mip1"]
    cluster = max(round(kw["cluster"] * sz.mip1_n / kw["n"]), 1)
    return gen(n=sz.mip1_n, nnz_target=sz.mip1_nnz, cluster=cluster,
               seed=seed)


def _spmm_ref_rows(rows, cols, data, b, start, r0, *, n_rows, n_cols,
                   length):
    """Rows [r0, r0 + n_rows) of A @ B: those rows of A densified from the
    COO triplets starting at ``start``, then one HIGHEST matmul."""
    r = jax.lax.dynamic_slice(rows, (start,), (length,)) - r0
    c = jax.lax.dynamic_slice(cols, (start,), (length,))
    v = jax.lax.dynamic_slice(data, (start,), (length,))
    ok = (r >= 0) & (r < n_rows)
    a = jnp.zeros((n_rows, n_cols), jnp.float32).at[
        jnp.where(ok, r, 0), c].add(jnp.where(ok, v, 0.0))
    return jnp.dot(a, b, precision=HIGHEST)


def _sddmm_ref_blocks(xb, yb, rid, cid):
    return jnp.einsum("shn,swn->shw", xb[rid], yb[cid], precision=HIGHEST)


def _attn_ref_rows(q, k, v, q0, *, band):
    """Dense banded-causal softmax attention for query rows [q0, q0+C)."""
    C, d = q.shape[0], q.shape[-1]
    s = jnp.einsum("chd,lhd->hcl", q, k, precision=HIGHEST) * d ** -0.5
    qp = q0 + jnp.arange(C)[:, None]
    kp = jnp.arange(k.shape[0])[None, :]
    s = jnp.where((kp <= qp) & (kp > qp - band), s, -jnp.inf)
    return jnp.einsum("hcl,lhd->chd", jax.nn.softmax(s, axis=-1), v,
                      precision=HIGHEST)


def library_phase(ck: Checks, sz: Sizes, interpret: bool = False) -> None:
    ph = "library"
    t0 = time.perf_counter()
    csr = mip1_csr(sz)
    a = bcsr_lib.from_scipy(csr, sz.block)
    nnzb_before = a.nnzb
    arrays, meta = ops.prepare_sparse(a, jnp.float32, reorder="jaccard")
    del a
    ck.line(ph, f"mip1 n={csr.shape[0]} nnz={csr.nnz} block={sz.block}: "
                f"nnzb {nnzb_before} -> {meta.nnzb} after the Jaccard "
                f"reorder ({100 * (1 - meta.nnzb / nnzb_before):.1f}% fewer), "
                f"max_bpr={meta.max_bpr}; host prep "
                f"{time.perf_counter() - t0:.1f}s")
    ck.check(ph, "reorder", meta.nnzb < nnzb_before,
             "the reorder removed blocks")
    M, K = csr.shape
    h, w = sz.block

    # device COO of A for the reference (padded so every slice is in range)
    coo = csr.tocoo()
    starts = list(range(0, M, sz.ref_rows))
    ends = [min(r0 + sz.ref_rows, M) for r0 in starts]
    length = max(int(csr.indptr[e] - csr.indptr[s])
                 for s, e in zip(starts, ends))
    pad = np.zeros(length, np.int64)
    rows = jnp.asarray(np.concatenate([coo.row, pad - 1]), jnp.int32)
    cols = jnp.asarray(np.concatenate([coo.col, pad]), jnp.int32)
    data32 = jnp.asarray(np.concatenate([coo.data, pad]), jnp.float32)
    ref_rows = jax.jit(_spmm_ref_rows,
                       static_argnames=("n_rows", "n_cols", "length"))
    ref_blocks = jax.jit(_sddmm_ref_blocks)
    key = jax.random.PRNGKey(0)

    for dtype in (jnp.float32, jnp.bfloat16):
        dname = jnp.dtype(dtype).name
        label = PRECISION[dname]
        arrs = arrays._replace(vals=arrays.vals.astype(dtype))
        data = data32.astype(dtype).astype(jnp.float32)
        for n in sz.ns:
            key, kb, kx, ky = jax.random.split(key, 4)
            # ---- SpMM: C = A @ B
            b = jax.random.normal(kb, (K, n), jnp.float32).astype(dtype)
            outs = _both_schedules(lambda be: lambda ar, bb: ops.spmm(
                ar, meta, bb, backend=be, interpret=interpret), arrs, b)
            errs = {be: ErrMax() for be in outs}
            b32 = b.astype(jnp.float32)
            for r0, r1 in zip(starts, ends):
                ref = ref_rows(rows, cols, data, b32,
                               int(csr.indptr[r0]), r0, n_rows=sz.ref_rows,
                               n_cols=K, length=length)[: r1 - r0]
                for be, (out, _, _) in outs.items():
                    errs[be].add(out[r0:r1], ref)
            for be, (_, secs, nk) in outs.items():
                _report(ck, ph, f"spmm {be} {label} N={n}", errs[be],
                        TOL[dname], secs, nk, interpret)
            del outs
            # ---- SDDMM: blocks of X Y^T at the stored structure
            x = jax.random.normal(kx, (M, n), jnp.float32).astype(dtype)
            y = jax.random.normal(ky, (K, n), jnp.float32).astype(dtype)
            outs = _both_schedules(lambda be: lambda ar, xx, yy: ops.sddmm(
                ar, meta, xx, yy, backend=be, out_dtype=jnp.float32,
                interpret=interpret), arrs, x, y)
            xp = jnp.zeros((meta.n_block_rows * h, n), jnp.float32).at[:M].set(
                x.astype(jnp.float32)[arrs.row_perm])
            yp = jnp.zeros((meta.n_block_cols * w, n), jnp.float32).at[:K].set(
                y.astype(jnp.float32))
            xb = xp.reshape(meta.n_block_rows, h, n)
            yb = yp.reshape(meta.n_block_cols, w, n)
            errs = {be: ErrMax() for be in outs}
            for s0 in range(0, meta.nnzb, sz.ref_entries):
                s1 = min(s0 + sz.ref_entries, meta.nnzb)
                ref = ref_blocks(xb, yb, arrs.row_ids[s0:s1],
                                 arrs.col_ids[s0:s1])
                ref = ref * arrs.real_mask[s0:s1, None, None]
                for be, (out, _, _) in outs.items():
                    errs[be].add(out[s0:s1], ref)
            for be, (_, secs, nk) in outs.items():
                _report(ck, ph, f"sddmm {be} {label} N={n}", errs[be],
                        TOL[dname], secs, nk, interpret)
            del outs, x, y, xp, yp, xb, yb

    del arrays, rows, cols, data32
    _attention(ck, sz, interpret)


def _both_schedules(make_fn, *args):
    """``{backend: (output, compile seconds, n kernels)}`` for the two
    Pallas schedules, nnz_stream (``pallas``) and ``row_loop``."""
    outs = {}
    for be in ("pallas", "row_loop"):
        compiled, secs, nk = _compile(make_fn(be), *args)
        outs[be] = (compiled(*args), secs, nk)
    return outs


def _report(ck, ph, name, err: ErrMax, tol, secs, n_kernels, interpret):
    kernel = "interpret" if interpret else f"{n_kernels} tpu_custom_call"
    ok = err.rel <= tol and (interpret or not _on_tpu() or n_kernels > 0)
    ck.check(ph, name, ok,
             f"max|err|/max|ref| = {err.rel:.3e} (tol {tol:g}), {kernel}, "
             f"compile {secs:.2f}s")


def _attention(ck: Checks, sz: Sizes, interpret: bool) -> None:
    ph = "library"
    Lq, H, d = sz.attn_len, sz.attn_heads, sz.attn_head_dim
    spec = A.AttnSparsitySpec(mask=A.banded(sz.attn_band),
                              block=sz.attn_block, backend="fused",
                              interpret=interpret)
    impl = A.resolve_attn_impl(spec, Lq, d)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kx, (1, Lq, H, d), jnp.float32)
               for kx in (kq, kk, kv))
    t0 = time.perf_counter()
    out = jax.block_until_ready(jax.jit(
        lambda q, k, v: A.block_sparse_attention(q, k, v, spec))(q, k, v))
    secs = time.perf_counter() - t0
    ref_fn = jax.jit(_attn_ref_rows, static_argnames=("band",))
    err = ErrMax()
    for q0 in range(0, Lq, sz.attn_ref_rows):
        q1 = min(q0 + sz.attn_ref_rows, Lq)
        err.add(out[0, q0:q1], ref_fn(q[0, q0:q1], k[0], v[0], q0,
                                      band=sz.attn_band))
    ck.check(ph, f"block_sparse_attention {impl} L={Lq} "
                 f"banded({sz.attn_band}) H={H} d={d}",
             impl == "fused" and err.rel <= ATTN_TOL,
             f"max|err|/max|ref| = {err.rel:.3e} (tol {ATTN_TOL:g}), "
             f"first call incl. compile {secs:.2f}s")


# ------------------------------------------------------------ server phase
class _WatchedDecode:
    """Wraps the engine's jitted decode: times the first call (compile
    included) and keeps a device flag per step that its logits are
    finite."""

    def __init__(self, decode):
        self.decode = decode
        self.first_s = None
        self.finite = []

    def __call__(self, *args):
        t0 = time.perf_counter()
        logits, cache = self.decode(*args)
        if self.first_s is None:
            jax.block_until_ready(logits)
            self.first_s = time.perf_counter() - t0
        self.finite.append(jnp.isfinite(logits).all())
        return logits, cache


def server_phase(ck: Checks, sz: Sizes, cfg) -> None:
    ph = "server"
    spec = cfg.ffn_sparsity
    meta_in, meta_out = L.mlp_sparse_metas(
        spec, cfg.d_model, cfg.d_ff, T._mlp_seed_hints(cfg))
    picks = {name: ops.resolve_backend(spec.backend, spec.bn, m, sz.slots)
             for name, m in (("gate", meta_in), ("up", meta_in),
                             ("down", meta_out))}
    ck.check(ph, "sparse FFN picks", all(
        be in ("pallas", "row_loop") for be, _ in picks.values()) and
        not (spec.interpret and _on_tpu()),
        ", ".join(f"{k}: {be}/bn{bn}" for k, (be, bn) in picks.items()) +
        f" (backend={spec.backend!r}, interpret={spec.interpret})")

    t0 = time.perf_counter()
    params = T.init_params(cfg, seed=0)
    engine = ServeEngine(cfg, params, n_slots=sz.slots,
                         cache_len=sz.cache_len)
    jax.block_until_ready((params, engine.cache))
    ck.line(ph, f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
                f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
                f"params + KV cache ({sz.slots} slots x {sz.cache_len}) "
                f"ready in {time.perf_counter() - t0:.1f}s")
    groups = engine.paged_kv.report()["groups"] if engine.paged_kv else []
    ck.check(ph, "decode path", bool(groups) and all(
        g["paged"] for g in groups), "; ".join(
        f"{g['group']}: " + (f"paged, {g['pages_touched_per_step']}/"
                             f"{g['n_pages']} pages per step"
                             if g["paged"] else "dense-bias")
        for g in groups) or "no block-sparse attention")

    watch = _WatchedDecode(engine._decode)
    engine._decode = watch
    requests = serve_cli.make_requests(cfg, sz.requests, sz.prompt_len,
                                       sz.new_tokens, seed=0)
    streamed, secs = serve_cli.generate_all(engine, requests)
    n_tok = sum(len(t) for t in streamed.values())
    complete = (len(streamed) == sz.requests and all(
        len(t) == sz.new_tokens and all(0 <= x < cfg.vocab_size for x in t)
        for t in streamed.values()))
    finite = bool(jnp.stack(watch.finite).all())
    ck.check(ph, "requests", complete and finite,
             f"{len(streamed)}/{sz.requests} finished with {sz.new_tokens} "
             f"in-range tokens each, logits finite in all "
             f"{len(watch.finite)} decode calls")
    ck.line(ph, f"single cold run, not a benchmark: first decode call "
                f"(compile included) {watch.first_s:.2f}s; {n_tok} tokens "
                f"in {secs:.2f}s = {n_tok / secs:.1f} tok/s cold, "
                f"{n_tok / max(secs - watch.first_s, 1e-9):.1f} tok/s "
                f"after the first call")

    # one decode step at the engine's final state: configured kernels vs
    # the xla backend (cache donated through both: each step rewrites
    # the same slot, so the second sees the state the first saw)
    cache, engine.cache = engine.cache, None
    toks = jnp.asarray([streamed[r.rid][-1] for r in requests[-sz.slots:]],
                       jnp.int32)
    pos = jnp.asarray(sz.prompt_len + sz.new_tokens - 1, jnp.int32)
    logits = {}
    for name, c in (("kernels", cfg), ("xla", xla_lowered(cfg))):
        step = jax.jit(lambda p, kv, t, i, _c=c: T.decode_step(_c, p, kv, t, i),
                       donate_argnums=(1,))
        t0 = time.perf_counter()
        compiled = step.lower(params, cache, toks, pos).compile()
        secs = time.perf_counter() - t0
        nk = compiled.as_text().count("tpu_custom_call")
        logits[name], cache = compiled(params, cache, toks, pos)
        ck.line(ph, f"decode step [{name}]: compile {secs:.2f}s, "
                    f"{nk} tpu_custom_call")
        if name == "kernels" and _on_tpu():
            ck.check(ph, "Pallas in decode step", nk > 0,
                     f"{nk} tpu_custom_call in the compiled step")
    err = ErrMax()
    err.add(logits["kernels"], logits["xla"])
    agree = int(jnp.sum(jnp.argmax(logits["kernels"], -1) ==
                        jnp.argmax(logits["xla"], -1)))
    ck.check(ph, "decode step vs xla", bool(
        jnp.isfinite(logits["kernels"]).all()) and err.rel <= LOGITS_TOL,
        f"max|dlogits|/max|logits| = {err.rel:.3e} (tol {LOGITS_TOL:g}), "
        f"argmax agrees on {agree}/{sz.slots} slots")


# ----------------------------------------------------------- sharded phase
def _place_sharded(arrays: dist_spmm.ShardedArrays, mesh):
    """This script's own input placement (``dist_spmm`` places nothing):
    per-shard leaves over the mesh's row axis, the rest replicated."""
    row = NamedSharding(mesh, P(dist_spmm.AXIS_ROW))
    rep = NamedSharding(mesh, P())
    per_shard = {"src_index", "row_ids", "col_ids", "real_mask", "t_perm",
                 "t_row_ids", "t_col_ids"}
    return arrays._replace(**{
        f: jax.device_put(getattr(arrays, f), row if f in per_shard else rep)
        for f in arrays._fields if getattr(arrays, f) is not None})


def sharded_phase(ck: Checks, sz: Sizes, n_shards: int,
                  interpret: bool = False) -> None:
    ph = "sharded"
    t0 = time.perf_counter()
    csr = mip1_csr(sz)
    perm = permute.SCHEMES["jaccard"](csr, block=sz.block)
    a = bcsr_lib.from_scipy(csr[perm].tocsr(), sz.block)
    sharr, smeta = dist_spmm.prepare_sharded(a, n_shards)
    mesh = dist_spmm.make_spmm_mesh(n_shards)
    sharr = _place_sharded(sharr, mesh)
    arrays, meta = ops.prepare_sparse(a)
    bal = dist_spmm.shard_balance_stats(a, n_shards)
    ck.line(ph, f"mip1 (Jaccard-reordered) nnzb={a.nnzb} over {n_shards} "
                f"shards: loads {bal['loads']} (imbalance "
                f"{bal['imbalance']}), {smeta.nnzb_per_shard} slots per "
                f"shard; host prep {time.perf_counter() - t0:.1f}s")
    b = jax.random.normal(jax.random.PRNGKey(2), (a.shape[1], sz.sharded_n),
                          jnp.float32).astype(jnp.bfloat16)
    outs = {}
    for k in (1, 2):
        fn = jax.jit(lambda arrs, bb, _k=k: dist_spmm.spmm_sharded(
            arrs, smeta, bb, backend="auto", mesh=mesh, n_chunks=_k,
            interpret=interpret))
        t0 = time.perf_counter()
        outs[k] = jax.block_until_ready(fn(sharr, b))
        ck.line(ph, f"spmm_sharded n_chunks={k}: first call incl. compile "
                    f"{time.perf_counter() - t0:.2f}s")
    out = outs[1]
    # where C lives: on every mesh device (replicated today, since the
    # final un-permute is a global take), never gathered onto one
    out_devs = {s.device for s in out.addressable_shards}
    layout = ("replicated" if out.sharding.is_fully_replicated
              else "row-sharded")
    ck.check(ph, "output placement", out_devs == set(mesh.devices.flat),
             f"{layout} ({out.sharding}) on {len(out_devs)} of {n_shards} "
             "mesh devices: " + ", ".join(
                 f"{s.device} rows {s.index[0].start or 0}:"
                 f"{s.index[0].stop or out.shape[0]}"
                 for s in out.addressable_shards))
    stats = [(d.id, (d.memory_stats() or {}).get("peak_bytes_in_use"))
             for d in mesh.devices.flat]
    ck.line(ph, "peak bytes in use per device: " + ", ".join(
        f"{i}: {p}" for i, p in stats))
    ck.check(ph, "chunked == unchunked", bool(np.array_equal(
        np.asarray(outs[2]).view(np.uint16),
        np.asarray(outs[1]).view(np.uint16))), "bit for bit (bf16 views)")
    single = jax.jit(lambda ar, bb: ops.spmm(ar, meta, bb, backend="auto",
                                            interpret=interpret))
    ref = single(jax.device_put(arrays, jax.devices()[0]),
                 jax.device_put(b, jax.devices()[0]))
    err = ErrMax()
    err.add(np.asarray(out, np.float32), np.asarray(ref, np.float32))
    ck.check(ph, "sharded vs single-device ops.spmm",
             err.rel <= TOL["bfloat16"],
             f"max|err|/max|ref| = {err.rel:.3e} (tol {TOL['bfloat16']:g})")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded SpMM over a 4-chip mesh")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if args.chips > jax.device_count():
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"[device] {dev.device_kind} x{jax.device_count()}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    ck = Checks()
    if args.chips == 4:
        ck.run("sharded", sharded_phase, FULL, 4)
    else:
        ck.run("library", library_phase, FULL)
        jax.clear_caches()
        ck.run("server", server_phase, FULL, get_config("smat-attn-1.3b"))
    if ck.failed:
        print(f"chip_smoke: FAILED {ck.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
