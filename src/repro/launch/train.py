"""Training launcher CLI.

Examples:
  # end-to-end ~100M-param sparse-FFN LM for a few hundred steps on CPU:
  PYTHONPATH=src python -m repro.launch.train --arch smat-ffn-1.3b:smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

  # failure injection + automatic restart from the latest checkpoint:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b:smoke \
      --steps 60 --batch 4 --seq 64 --ckpt-dir /tmp/ckpt2 \
      --inject-failure 30
"""
from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw
from repro.train.loop import train_with_restarts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--inject-failure", type=int, default=None)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots"])
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 1,1 (default: all local devices on data)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    cfg = get_config(args.arch)
    shape = ShapeCell("cli", "train", args.seq, args.batch)

    from repro.core.sparse_linear import is_sharded
    if cfg.ffn_sparsity is not None and is_sharded(cfg.ffn_sparsity):
        # partitioned sparse FFN: surface the per-shard balance and the
        # autotune picks the model path will dispatch with (the static
        # metas mlp() derives — the same ones the train step traces against)
        from repro.launch.dryrun import sparse_shard_report
        rep = sparse_shard_report(cfg, n_tokens=args.batch * args.seq)
        for lname, r in rep.items():
            logging.getLogger("train").info(
                "sparse FFN [%s]: %d shards, nnzb loads %s, auto picks %s",
                lname, r["n_shards"], r["loads"], r["auto_picks"])

    def mesh_factory(restart_idx: int):
        if args.mesh_shape:
            dims = tuple(int(x) for x in args.mesh_shape.split(","))
        else:
            n = len(jax.devices())
            dims = (n, 1)
        return mesh_lib.make_mesh(dims, ("data", "model"))

    res = train_with_restarts(
        cfg, shape, mesh_factory,
        total_steps=args.steps,
        opt_cfg=adamw.AdamWConfig(lr=args.lr, total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        fail_at_step=args.inject_failure,
        max_restarts=args.max_restarts, remat=args.remat)
    print(f"[train] done: {res.final_step} steps, "
          f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}, "
          f"restarts={res.restarts_used}, stragglers={res.straggler_steps}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
