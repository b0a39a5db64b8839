"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs (no allocation) and extract memory / cost / roofline.

A CPU-only shape and memory tool: ``main()`` sets ``XLA_FLAGS`` (from
``DRYRUN_XLA_FLAGS``, default 512 virtual host devices) before JAX
initializes its backend, and lowers every sparse kernel as
``backend="xla"`` (no Pallas kernel compiles for the CPU).  Importing the
module changes nothing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
Small-mesh testing (CI):
  DRYRUN_XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.dryrun --arch h2o-danube-1.8b:smoke \
      --shape train_4k --mesh-shape 2,4 --batch 8 --seq 128
"""
import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (SHAPES, cell_applicable, get_config, list_archs,
                           xla_lowered)
from repro.configs.base import ShapeCell
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch import sharding as sh
from repro.launch import steps as st
from repro.models import transformer as T
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.optim import adamw


def _lower_and_compile(cfg, shape: ShapeCell, mesh, remat: str,
                       seq_shard_long: bool, donate: bool):
    t0 = time.time()
    params_specs = T.param_specs(cfg)
    # inference cells use serve-mode weight shardings (TP only, no FSDP —
    # §Perf cell A).  Replication only amortizes over batch: single-request
    # long-context keeps the sharded (train) weight layout.
    p_mode = "serve" if (shape.kind != "train" and
                         shape.global_batch >= 8) else "train"
    p_shard = sh.param_shardings(mesh, params_specs, mode=p_mode)

    with mesh:
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            o_specs = jax.eval_shape(adamw.init, params_specs)
            o_shard = sh.opt_state_shardings(mesh, o_specs, p_shard)
            b_specs = st.input_specs(cfg, shape)
            b_shard = sh.batch_shardings(mesh, b_specs)
            fn = st.make_train_step(cfg, opt_cfg, remat=remat)
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(params_specs, o_specs, b_specs)
        elif shape.kind == "prefill":
            b_specs = st.input_specs(cfg, shape)
            b_shard = sh.batch_shardings(mesh, b_specs)
            c_specs = T.cache_specs(cfg, shape.global_batch, shape.seq_len)
            c_shard = sh.cache_shardings(mesh, c_specs, cfg)
            fn = st.make_prefill_step(cfg, shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard),
                             out_shardings=(None, c_shard))
            lowered = jitted.lower(params_specs, b_specs)
        else:  # decode
            seq_shard = seq_shard_long and shape.global_batch < 8
            b_specs = st.input_specs(cfg, shape)
            tok_shard = sh.batch_shardings(mesh, b_specs)["tokens"] \
                if shape.global_batch >= 8 else None
            c_specs = T.cache_specs(cfg, shape.global_batch, shape.seq_len)
            c_shard = sh.cache_shardings(mesh, c_specs, cfg,
                                         seq_shard=seq_shard)
            fn = st.make_decode_step(cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, c_shard, tok_shard, None),
                out_shardings=(None, c_shard),
                donate_argnums=(1,) if donate else ())
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jitted.lower(params_specs, c_specs,
                                   b_specs["tokens"], pos_spec)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def _cell_costs(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = rl.parse_collectives(compiled.as_text())
    return flops, bytes_acc, coll


def _repeat_knobs(cfg) -> dict:
    """Layer-stack repeat counts (the affine variables of the cost model)."""
    if cfg.layout == "zamba":
        return {"hybrid_n_units": cfg.hybrid_n_units,
                "hybrid_tail": cfg.hybrid_tail}
    if cfg.layout == "gemma_pair":
        return {"n_layers": cfg.n_layers // 2}   # repeats = pairs
    return {"n_layers": cfg.n_layers}


def _with_repeats(cfg, reps: dict):
    import dataclasses as dc
    kw = dict(reps)
    if cfg.layout == "gemma_pair" and "n_layers" in kw:
        kw["n_layers"] = kw["n_layers"] * 2
    return dc.replace(cfg, **kw)


def extrapolated_costs(cfg, shape: ShapeCell, mesh, remat: str,
                       seq_shard_long: bool, verbose: bool = True):
    """XLA counts while-loop bodies once, so scanned stacks undercount
    FLOPs/bytes/collectives.  Compile small UNROLLED variants (1 and 2
    repeats per scan knob) and extrapolate affinely to the real depth.
    Returns (flops, bytes, wire_bytes, collective_dict) per device."""
    from repro.models import unroll as U
    knobs = _repeat_knobs(cfg)
    names = list(knobs)

    def measure(reps):
        small = _with_repeats(cfg, reps)
        with U.unroll_scans():
            compiled, _, _ = _lower_and_compile(
                small, shape, mesh, remat, seq_shard_long, donate=False)
        return _cell_costs(compiled)

    base_reps = {k: 1 for k in names}
    f0, b0, c0 = measure(base_reps)
    flops, bytes_acc, wire = f0, b0, c0.wire_bytes
    coll_counts = dict(c0.counts)
    for k in names:
        reps2 = dict(base_reps)
        reps2[k] = 2
        f1, b1, c1 = measure(reps2)
        extra = knobs[k] - 1
        flops += (f1 - f0) * extra
        bytes_acc += (b1 - b0) * extra
        wire += (c1.wire_bytes - c0.wire_bytes) * extra
        for kind, n in c1.counts.items():
            coll_counts[kind] = coll_counts.get(kind, 0) + \
                (n - c0.counts.get(kind, 0)) * extra
    coll = {"counts": coll_counts, "wire_bytes": wire,
            "mode": "extrapolated-unroll"}
    if verbose:
        print(f"[dryrun]   cost-extrapolation {cfg.name} x {shape.name}: "
              f"flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e} "
              f"wire/dev={wire:.3e}")
        sys.stdout.flush()
    return flops, bytes_acc, wire, coll


def run_cell(cfg, shape: ShapeCell, mesh, *, remat: str = "full",
             seq_shard_long: bool = True, donate: bool = True,
             extrapolate: bool = True, verbose: bool = True) -> dict:
    """Lower + compile one cell; returns the record for EXPERIMENTS.md."""
    n_dev = mesh.devices.size
    compiled, t_lower, t_compile = _lower_and_compile(
        cfg, shape, mesh, remat, seq_shard_long, donate)

    mem = compiled.memory_analysis()
    flops_raw, bytes_raw, coll_raw = _cell_costs(compiled)
    if extrapolate:
        flops, bytes_acc, wire, coll_d = extrapolated_costs(
            cfg, shape, mesh, remat, seq_shard_long, verbose=verbose)
    else:
        flops, bytes_acc, wire = flops_raw, bytes_raw, coll_raw.wire_bytes
        coll_d = coll_raw.to_dict()
    model_flops = rl.model_flops_for(cfg, shape)
    roof = rl.compute_roofline(flops, bytes_acc, wire, n_dev,
                               model_flops, collectives=coll_d)

    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "status": "ok",
        "remat": remat,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes +
                                         mem.temp_size_in_bytes),
        },
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(f"[dryrun] {cfg.name} x {shape.name} @ {rec['mesh']}: "
              f"compile={t_compile:.0f}s "
              f"mem/dev={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
              f"Tc={roof.t_compute*1e3:.2f}ms Tm={roof.t_memory*1e3:.2f}ms "
              f"Tx={roof.t_collective*1e3:.2f}ms -> {roof.bottleneck}")
        sys.stdout.flush()
    return rec


def sparse_shard_report(cfg, n_tokens: int = 512) -> dict:
    """Per-shard nnzb balance AND autotune kernel picks of the arch's
    partitioned sparse FFN (``SparsitySpec(shards=...)``) — empty when the
    arch has none.  Printed per arch so the LPT partition quality and the
    per-shard variant choices are visible before any launch.

    The picks come from the SAME static metas the model path dispatches
    on (``models.layers.mlp_sparse_metas`` — true per-shard structure
    stats merged over the layer stack), resolved as ``backend="auto"``
    for an ``n_tokens``-wide activation panel.  ``shards="auto"`` specs
    additionally report the RESOLVED shard count per weight (the
    autotuner's shard-count pick) and every report carries the overlap
    chunk schedule the apply will pipeline the token panel with."""
    spec = cfg.ffn_sparsity
    from repro.core import sparse_linear as sl
    if spec is None or not sl.is_sharded(spec):
        return {}
    from repro.kernels import ops as kops
    from repro.launch import dist_spmm
    from repro.models import layers as L
    from repro.models.transformer import _mlp_seed_hints
    # balance and picks must describe the SAME structures: use the real
    # pattern seeds of the first layer's gate / down weights (mlp_seed),
    # not shard_balance_report's default probe seed
    seed0 = L.mlp_seed(_mlp_seed_hints(cfg)[0])
    rep = {
        "gate_up": sl.shard_balance_report(cfg.d_model, cfg.d_ff, spec,
                                           seed=seed0),
        "down": sl.shard_balance_report(cfg.d_ff, cfg.d_model, spec,
                                        seed=seed0 + 2),
    }
    n_chunks = max(spec.shard_chunks, 1)
    for lname, (od, idim) in (("gate_up", (cfg.d_ff, cfg.d_model)),
                              ("down", (cfg.d_model, cfg.d_ff))):
        rep[lname]["resolved_shards"] = sl.resolved_shards(spec, od, idim)
        rep[lname]["shards_auto"] = spec.shards == "auto"
        rep[lname]["n_chunks"] = n_chunks
        rep[lname]["chunk_schedule"] = [
            list(c) for c in dist_spmm.chunk_schedule(n_tokens, n_chunks)]
    meta_in, meta_out = L.mlp_sparse_metas(
        spec, cfg.d_model, cfg.d_ff, _mlp_seed_hints(cfg))
    from repro.analysis import verify_launch as vl
    for lname, m in (("gate_up", meta_in), ("down", meta_out)):
        rep[lname]["auto_picks"] = [
            "{}/bn{}".format(*kops.resolve_backend("auto", spec.bn, sm,
                                                   n_tokens))
            for sm in m.shard_metas]
        # static contract re-proof: the same checks REPRO_VERIFY_LAUNCH=1
        # would run at dispatch, surfaced in the pre-launch report
        rep[lname]["verify"] = vl.verify_summary(m, n_tokens)
    return rep


def sparse_attention_report(cfg, seq_len: int = 512) -> dict:
    """Mask structure + autotune picks of the arch's block-sparse attention
    (``ModelConfig.attn_sparsity``) — empty when the arch has none.

    Reports the mask nnzb / block density vs dense-causal, the
    attention-level fused-vs-composed resolution (v6 ``op=attn`` family —
    the PR-6 one-kernel path), and the composed ``op=sddmm`` (score) +
    ``op=spmm`` (context) picks the spec's backend resolves for a
    ``seq_len`` sequence at the arch's REAL head dim (the contraction
    width the runtime ops fingerprint with) — the attention twin of
    ``sparse_shard_report``, derived entirely from static metas (the
    PR-4/PR-5 pipeline: no params, no arrays)."""
    spec = getattr(cfg, "attn_sparsity", None)
    if spec is None:
        return {}
    from repro.analysis import verify_launch as vl
    from repro.analysis import workspace
    from repro.models import attention as A
    seq = max(seq_len, spec.block[0] * 2)   # at least two block-rows
    rep = A.attention_mask_report(spec, seq, head_dim=cfg.head_dim)
    meta = A.attention_mask_meta(spec.mask, seq, spec.block)
    # shared estimator (repro.analysis.workspace — same numbers the
    # attention benchmark gates on) + the static contract re-proof
    rep["composed_workspace_bytes"] = \
        workspace.attn_composed_workspace_bytes(meta)
    rep["fused_state_bytes"] = \
        workspace.attn_fused_state_bytes(spec.block, cfg.head_dim)
    rep["verify"] = vl.verify_summary(meta, cfg.head_dim, op="attn")
    return rep


def paged_kv_report(cfg, cache_len: int = 512, n_slots: int = 4) -> dict:
    """Paged block-sparse KV accounting for serving (PR 8) — empty when
    the arch has no ``attn_sparsity`` or no k/v attention rings.

    Per layer group: page count and bytes, pages touched per decode step
    (the mask meta's ``max_bpr`` — the page table IS the mask BCSR),
    device-resident vs host-offloaded bytes under the analytic placement
    policy, and the cost-model step-read estimates.  Derived entirely
    from static metas, like the other sparse reports; also round-trips
    the materialized page tables through ``sharding.cache_shardings`` so
    the page-table leaf rules stay exercised."""
    spec = getattr(cfg, "attn_sparsity", None)
    if spec is None or cfg.layout not in ("attn_mlp", "gemma_pair"):
        return {}
    from repro.serve.paged_kv import PagedKVCache  # local: layering
    paged = PagedKVCache(cfg, cache_len, n_slots)
    rep = paged.report()
    leaves = paged.table_leaves()
    if leaves:
        mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
        shardings = sh.cache_shardings(mesh, leaves, cfg)
        rep["table_leaf_specs"] = {
            g: {k: str(s.spec) for k, s in d.items()}
            for g, d in shardings.items()}
    return rep


def main(argv=None):
    # the device count locks when JAX first initializes its backend
    os.environ["XLA_FLAGS"] = os.environ.get(
        "DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="override, e.g. 2,4 (axes data,model) or 2,2,2")
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots", "names"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-seq-shard-long", action="store_true")
    ap.add_argument("--no-extrapolate", action="store_true",
                    help="skip the unrolled cost-extrapolation compiles "
                         "(multi-pod pass = sharding/memory proof only)")
    args = ap.parse_args(argv)

    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) == 3 \
            else ("data", "model")
        mesh = mesh_lib.make_mesh(shape, axes)
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    records = []
    for a in archs:
        cfg = get_config(a)
        with obs_trace.span("dryrun.shard_report", arch=cfg.name):
            shard_rep = sparse_shard_report(cfg)
        if shard_rep:
            for lname, r in shard_rep.items():
                print(f"[dryrun] {cfg.name} sparse shard balance [{lname}]: "
                      f"{r['n_shards']} shards, nnzb loads {r['loads']} "
                      f"(imbalance {r['imbalance']}x vs contiguous "
                      f"{r['contig_imbalance']}x), "
                      f"auto picks {r['auto_picks']}")
            records.append({"arch": cfg.name, "status": "sparse_shards",
                            "sparse_shards": shard_rep})
        with obs_trace.span("dryrun.attention_report", arch=cfg.name):
            attn_rep = sparse_attention_report(cfg)
        if attn_rep:
            print(f"[dryrun] {cfg.name} sparse attention mask: "
                  f"{attn_rep['mask']['kind']} nnzb={attn_rep['nnzb']} "
                  f"({attn_rep['block_density_vs_causal']}x of dense-causal "
                  f"blocks at seq {attn_rep['seq_len']}), "
                  f"impl={attn_rep['attn_impl']} "
                  f"(attn={attn_rep['attn_pick']}), picks "
                  f"sddmm={attn_rep['sddmm_pick']} "
                  f"spmm={attn_rep['spmm_pick']}")
            records.append({"arch": cfg.name, "status": "sparse_attention",
                            "sparse_attention": attn_rep})
        with obs_trace.span("dryrun.paged_kv_report", arch=cfg.name):
            kv_rep = paged_kv_report(cfg)
        if kv_rep:
            for g in kv_rep["groups"]:
                extra = ("" if not g.get("paged") else
                         f", {g['pages_touched_per_step']}/{g['n_pages']} "
                         "pages/step")
                print(f"[dryrun] {cfg.name} paged KV [{g['group']}]: "
                      f"{g.get('n_pages', 0)} pages x "
                      f"{g.get('page_bytes', 0)} B, resident "
                      f"{g.get('resident_bytes', 0)} B over "
                      f"{g['n_layers']} layers (paged={g['paged']}{extra})")
            records.append({"arch": cfg.name, "status": "paged_kv",
                            "paged_kv": kv_rep})
        lowered_cfg = xla_lowered(cfg)
        if lowered_cfg is not cfg:
            print(f"[dryrun] {cfg.name}: sparse kernels lowered as "
                  "backend='xla' (CPU shape/memory tool; the chip runs the "
                  "configured Pallas kernels)")
            cfg = lowered_cfg
        for s in shapes:
            cell = SHAPES[s]
            if args.batch or args.seq:
                import dataclasses as dc
                cell = dc.replace(cell,
                                  global_batch=args.batch or cell.global_batch,
                                  seq_len=args.seq or cell.seq_len)
            ok, why = cell_applicable(cfg, cell)
            if not ok:
                records.append({"arch": cfg.name, "shape": cell.name,
                                "mesh": "x".join(
                                    str(x) for x in mesh.devices.shape),
                                "status": "skip", "reason": why})
                print(f"[dryrun] SKIP {cfg.name} x {cell.name}: {why}")
                continue
            try:
                with obs_trace.span("dryrun.cell", arch=cfg.name,
                                    shape=cell.name):
                    records.append(run_cell(
                        cfg, cell, mesh, remat=args.remat,
                        seq_shard_long=not args.no_seq_shard_long,
                        extrapolate=not args.no_extrapolate))
            except Exception as e:  # noqa
                traceback.print_exc()
                records.append({"arch": cfg.name, "shape": cell.name,
                                "status": "error", "error": repr(e)})

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records -> {args.out}")
    if obs_trace.enabled():
        print("[dryrun] trace summary:")
        print(obs_export.summary_tree(obs_trace.get_events()))
    n_err = sum(r["status"] == "error" for r in records)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
