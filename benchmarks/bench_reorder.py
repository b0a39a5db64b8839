"""Reorder-pipeline benchmark (paper Figs. 3-4 / Section VI-A) with a CI
regression gate, in the style of ``bench_autotune.py``.

For each structure case it reports:
  * nnzb reduction of the FAST clustering (``core.permute``, packed-bitmask
    greedy; native kernel when a C toolchain exists) vs the offline
    pure-Python reference (``core.reorder.jaccard_rows``);
  * clustering wall-clock of both and the speedup (the tentpole's >= 50x
    target is measured on the 4k-row clustered case);
  * permuted-vs-identity SpMM time through the transparent
    ``prepare_sparse(reorder=...)`` + ``spmm`` path.

Emits machine-readable JSON consumed by the CI diff step:

  python benchmarks/bench_reorder.py --smoke --out BENCH_reorder.json \
      --diff benchmarks/BENCH_reorder.baseline.json

``--diff`` checks (a) no baseline case disappeared, (b) on clustered cases
the fast reduction stays >= 95% of the reference's (computed fresh, so the
gate is falsifiable), and (c) the fast reduction stays >= 90% of the
committed baseline's.  Clustering SPEEDUP is wall-clock on a shared
runner — matching the autotune baseline's "report, never compare" policy
for absolute times, a 4k-row speedup below the expected floor prints a
WARNING but never fails CI (the nnzb-reduction gates above are the
deterministic, falsifiable ones).  Refresh the baseline with
``--out benchmarks/BENCH_reorder.baseline.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:  # runnable without a manual PYTHONPATH prefix
        sys.path.insert(0, _p)

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bcsr as bcsr_lib
from repro.core import native, permute, reorder, topology
from repro.kernels import ops
from repro.obs import metrics as obs_metrics

BLOCK = (16, 16)
TAU = 0.7
MAX_CANDIDATES = 4096
# expected 4k-case clustering speedup (>= 50x with the native kernel).
# Wall-clock on shared CI runners is not falsifiable — below this floor
# the diff prints a WARNING, it never fails (nnzb gates stay hard).
MIN_SPEEDUP_4K = 8.0
MIN_REDUCTION_VS_REF = 0.95
MIN_REDUCTION_VS_BASE = 0.90


def _cases(smoke: bool):
    """name -> (csr, clustered?).  The 4k-row clustered case anchors the
    clustering-speedup criterion in BOTH modes."""
    cases = [
        ("mip1_like_4k", topology.blocked_random(
            n=4096, nnz_target=160_000, cluster=32, seed=0), True),
        ("pdb1HYS_like", topology.blocked_random(
            n=2304, nnz_target=34_000, cluster=32, seed=1), True),
        ("conf5_band", topology.band(1536, 24), False),
        ("dc2_power_law", topology.power_law(2048, 6.0, seed=2), False),
    ]
    if not smoke:
        cases += [
            ("mip1_scaled_8k", topology.blocked_random(
                n=8192, nnz_target=163_000, cluster=64, seed=3), True),
        ]
    return cases


def _time_spmm(a: bcsr_lib.BCSR, reorder_scheme: str, n: int,
               iters: int = 3) -> float:
    arrays, meta = ops.prepare_sparse(
        a, dtype=jnp.float32, reorder=reorder_scheme, tau=TAU,
        max_candidates=MAX_CANDIDATES)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(
        (meta.shape[1], n)).astype(np.float32))
    fn = jax.jit(lambda bb: ops.spmm(arrays, meta, bb, backend="xla"))
    return obs_metrics.timeit(fn, b, warmup=1, iters=iters, reduce="min")


def run(smoke: bool = True) -> dict:
    rows = []
    for name, csr, clustered in _cases(smoke):
        a = bcsr_lib.from_scipy(csr, BLOCK)
        base = a.nnzb
        # fast clustering (min of 3: the permutation is deterministic)
        p_fast = permute.jaccard_rows_fast(
            csr, block_w=BLOCK[1], tau=TAU, max_candidates=MAX_CANDIDATES)
        t_fast = obs_metrics.timeit(
            permute.jaccard_rows_fast, csr, warmup=0, iters=3,
            reduce="min", block_w=BLOCK[1], tau=TAU,
            max_candidates=MAX_CANDIDATES)
        nnzb_fast = bcsr_lib.from_scipy(
            reorder.apply_perm(csr, p_fast), BLOCK).nnzb
        # offline reference (one run: it is the slow side being replaced)
        t0 = time.perf_counter()
        p_ref = reorder.jaccard_rows(csr, block_w=BLOCK[1], tau=TAU,
                                     max_candidates=MAX_CANDIDATES)
        t_ref = time.perf_counter() - t0
        nnzb_ref = bcsr_lib.from_scipy(
            reorder.apply_perm(csr, p_ref), BLOCK).nnzb
        # static-schedule length (n_block_rows * max_bpr) of the permuted
        # vs identity structure — clustering shrinks max_bpr, so a
        # (block-row x slot) schedule visits fewer (mostly-padding) slots.
        # Report-only per the gate policy (deterministic, but the nnzb
        # gates already pin clustering quality).
        m_id = ops.prepare_sparse_meta(a)
        m_ro = ops.prepare_sparse_meta(a, reorder="jaccard", tau=TAU,
                                       max_candidates=MAX_CANDIDATES)
        sched_id = m_id.n_block_rows * m_id.max_bpr
        sched_ro = m_ro.n_block_rows * m_ro.max_bpr
        # permuted-vs-identity SpMM through the transparent op path
        n = 64 if smoke else 128
        spmm_id = _time_spmm(a, "identity", n)
        spmm_ro = _time_spmm(a, "jaccard", n)
        row = {
            "name": name,
            "rows": int(csr.shape[0]),
            "clustered": clustered,
            "nnzb_base": int(base),
            "nnzb_fast": int(nnzb_fast),
            "nnzb_ref": int(nnzb_ref),
            "reduction_fast": round(base / max(nnzb_fast, 1), 3),
            "reduction_ref": round(base / max(nnzb_ref, 1), 3),
            "clustering_ms_fast": round(t_fast * 1e3, 3),
            "clustering_ms_ref": round(t_ref * 1e3, 3),
            "clustering_speedup": round(t_ref / max(t_fast, 1e-9), 1),
            "spmm_identity_us": round(spmm_id * 1e6, 1),
            "spmm_reordered_us": round(spmm_ro * 1e6, 1),
            "spmm_reordered_ratio": round(spmm_ro / max(spmm_id, 1e-12), 3),
            "sched_len_identity": int(sched_id),
            "sched_len_reordered": int(sched_ro),
            "sched_len_reduction": round(sched_id / max(sched_ro, 1), 3),
        }
        rows.append(row)
        print(f"{name:>16}: nnzb {base}->{nnzb_fast} "
              f"({row['reduction_fast']}x vs ref {row['reduction_ref']}x), "
              f"clustering {row['clustering_ms_fast']}ms vs "
              f"{row['clustering_ms_ref']}ms "
              f"({row['clustering_speedup']}x), spmm ratio "
              f"{row['spmm_reordered_ratio']}, static sched "
              f"{row['sched_len_identity']}->{row['sched_len_reordered']} "
              f"({row['sched_len_reduction']}x)", file=sys.stderr)
    return {
        "bench": "reorder",
        "mode": "smoke" if smoke else "full",
        "native_kernel": native.get_kernel() is not None,
        "block": list(BLOCK),
        "tau": TAU,
        "max_candidates": MAX_CANDIDATES,
        "cases": rows,
    }


def diff(result: dict, baseline: dict) -> int:
    """Regression diff; returns a process exit code."""
    got = {c["name"]: c for c in result["cases"]}
    want = {c["name"]: c for c in baseline["cases"]}
    failures = []
    for name in sorted(set(want) - set(got)):
        failures.append(f"case disappeared vs baseline: {name}")
    for name in sorted(set(got) - set(want)):
        print(f"note: new case not in baseline: {name}", file=sys.stderr)
    for name, c in got.items():
        if c["clustered"]:
            if c["reduction_fast"] < c["reduction_ref"] * MIN_REDUCTION_VS_REF:
                failures.append(
                    f"{name}: fast clustering reduction "
                    f"{c['reduction_fast']}x fell below the reference's "
                    f"{c['reduction_ref']}x")
            base = want.get(name)
            if base and c["reduction_fast"] < \
                    base["reduction_fast"] * MIN_REDUCTION_VS_BASE:
                failures.append(
                    f"{name}: reduction {c['reduction_fast']}x regressed "
                    f"vs committed baseline {base['reduction_fast']}x")
        if "4k" in name and c["clustering_speedup"] < MIN_SPEEDUP_4K:
            # wall-clock on shared runners: warn, never gate (absolute
            # times follow the autotune baseline's report-only policy)
            print(f"WARNING: {name}: clustering speedup "
                  f"{c['clustering_speedup']}x below the expected "
                  f"{MIN_SPEEDUP_4K}x (timing-only signal; not a failure)",
                  file=sys.stderr)
    if failures:
        print("REORDER REGRESSION:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"reorder diff OK: {len(got)} cases "
          f"(native_kernel={result.get('native_kernel')})", file=sys.stderr)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small case set / small N (CI job)")
    ap.add_argument("--out", default="BENCH_reorder.json",
                    help="where to write the results JSON")
    ap.add_argument("--diff", default=None, metavar="BASELINE",
                    help="after running, diff results against this baseline")
    args = ap.parse_args()

    result = run(args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}", file=sys.stderr)

    if args.diff:
        with open(args.diff) as f:
            baseline = json.load(f)
        return diff(result, baseline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
