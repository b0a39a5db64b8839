"""Benchmark runner — CI suite entrypoint + paper-figure modules.

CI suite mode (the single entrypoint the ``benchmark-smoke`` job runs):

  python benchmarks/run.py --smoke --diff-all

runs every gated benchmark (autotune, reorder, shard_scaling, sddmm,
attention, serving),
writes one ``BENCH_<name>.json`` each (a single combined artifact for CI),
diffs each against its committed ``benchmarks/BENCH_<name>.baseline.json``,
and exits nonzero if ANY diff fails.  Refresh a baseline with the
individual module's ``--out benchmarks/BENCH_<name>.baseline.json``.

Figure mode (``--figures [name,...]``, or legacy no flags = all): one
module per paper table/figure —

  bench_perf_model       — T_tot = T_e*n_e + T_init fit (Fig. 2 / SIII)
  bench_reorder          — reordering block-count effect (Figs. 3-4 / SVI-A)
  bench_suitesparse_like — SuiteSparse-pattern throughput (Fig. 8 / SVI-B)
  bench_band_sweep       — band sparsity sweep, dense crossover (Fig. 9)
  bench_n_scaling        — N scaling (Fig. 10 / SVI-D)
  bench_kernels          — Pallas kernel roofline table + dc2 study

Prints ``name,us_per_call,derived`` CSV.  These are slower, report-only
paper figures — CI runs the gated suite; ``tests/test_system.py`` keeps
the figure modules importable so they cannot silently rot.  Roofline
tables for the (arch x shape) cells come from ``repro.launch.dryrun``
(see its --out JSON + ``benchmarks/compare_sweeps.py`` for A/B tables).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:  # runnable without a manual PYTHONPATH prefix
        sys.path.insert(0, _p)

# gated CI benchmarks: (module name, baseline file)
SUITE = (
    ("bench_autotune", "BENCH_autotune.baseline.json"),
    ("bench_reorder", "BENCH_reorder.baseline.json"),
    ("bench_shard_scaling", "BENCH_shard_scaling.baseline.json"),
    ("bench_sddmm", "BENCH_sddmm.baseline.json"),
    ("bench_attention", "BENCH_attention.baseline.json"),
    ("bench_serving", "BENCH_serving.baseline.json"),
)

# report-only paper-figure modules (never gated; run via --figures)
FIGURES = ("bench_perf_model", "bench_reorder", "bench_suitesparse_like",
           "bench_band_sweep", "bench_n_scaling", "bench_kernels")


def run_suite(smoke: bool, diff_all: bool, out_dir: str = ".") -> int:
    import importlib

    from repro.obs import export as obs_export
    from repro.obs import trace as obs_trace

    rc = 0
    # the runner is an obs consumer: every suite module runs under a span,
    # and the run ends with the span tree of where its wall clock went
    with obs_trace.capture() as cap:
        for mod_name, baseline_name in SUITE:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            short = mod_name.replace("bench_", "")
            print(f"# === {short} ===", file=sys.stderr)
            with obs_trace.span(f"bench.{short}", smoke=smoke):
                result = mod.run(smoke)
            out_path = os.path.join(out_dir, f"BENCH_{short}.json")
            with open(out_path, "w") as f:
                json.dump(result, f, indent=1, sort_keys=True)
            print(f"wrote {out_path}", file=sys.stderr)
            if diff_all:
                baseline_path = os.path.join(_HERE, baseline_name)
                with open(baseline_path) as f:
                    baseline = json.load(f)
                rc |= mod.diff(result, baseline)
    print(obs_export.summary_tree(cap.events), file=sys.stderr)
    return rc


def run_figures(names=None) -> None:
    import importlib
    names = tuple(names or FIGURES)
    bad = [n for n in names if n not in FIGURES]
    if bad:  # validate up front — these modules run for minutes each
        raise SystemExit(f"unknown figure module(s) {bad}; "
                         f"pick from {FIGURES}")
    t0 = time.time()
    for name in names:
        print(f"# === {name} ===", file=sys.stderr)
        importlib.import_module(f"benchmarks.{name}").run()
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="suite mode, small cases (the CI job)")
    ap.add_argument("--full", action="store_true",
                    help="suite mode, full-size cases")
    ap.add_argument("--diff-all", action="store_true",
                    help="diff every suite result against its committed "
                         "baseline; exit nonzero on any regression")
    ap.add_argument("--out-dir", default=".",
                    help="where suite mode writes BENCH_*.json")
    ap.add_argument("--figures", nargs="*", default=None,
                    help="run the (report-only) paper-figure modules; "
                         "optionally name a subset, e.g. "
                         "--figures bench_kernels")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.figures is not None:
        run_figures(args.figures or None)
        return 0
    if args.smoke or args.full or args.diff_all:
        return run_suite(smoke=not args.full, diff_all=args.diff_all,
                         out_dir=args.out_dir)
    run_figures()
    return 0


if __name__ == "__main__":
    sys.exit(main())
