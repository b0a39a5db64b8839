"""Autotuned-dispatch benchmark: registry pick vs the hardcoded default.

For a suite of structure classes (band / power-law skew / uniform block
sparsity / near-dense), runs the ``repro.kernels.autotune`` micro-sweep and
reports the measured winner against the pre-registry hardcoded config
(nnz_stream, bn=512).  Because the sweep always measures the default too,
the cached pick is never slower than it (beyond the 2% tie-break band).

Emits machine-readable JSON (``BENCH_autotune.json``) consumed by the CI
regression-diff step:

  python benchmarks/bench_autotune.py --smoke --out BENCH_autotune.json \
      --diff benchmarks/BENCH_autotune.baseline.json

``--diff`` compares fresh results against a committed baseline: the case
set must match and every case must keep ``speedup_vs_default >= 0.9``
(absolute times are machine-specific and are NOT compared; refresh the
baseline with ``--out benchmarks/BENCH_autotune.baseline.json``).
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

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bcsr as bcsr_lib
from repro.core import topology
from repro.kernels import autotune, ops
from repro.obs import metrics as obs_metrics

# speedup below this vs the hardcoded default fails the regression gate;
# smoke mode (CI shared runners, interpret-mode timings) gets extra noise
# headroom — a genuinely wrong pick lands at 0.3-0.5x, far below either
MIN_SPEEDUP = 0.9
MIN_SPEEDUP_SMOKE = 0.75


def _time_config(arrays, meta, b, variant, bn, iters=3):
    """Wall-clock of one (variant, bn) config — a measurement pass
    INDEPENDENT of the tuner's selection sweep, so the speedup gate is
    falsifiable (a bad cached pick shows up here, it isn't >= default by
    construction)."""
    backend = autotune.get_variant(variant).backend
    fn = jax.jit(lambda bb: ops.spmm(arrays, meta, bb, backend=backend,
                                     bn=bn, interpret=True))
    # min: scheduler noise only ever adds time
    return obs_metrics.timeit(fn, b, warmup=1, iters=iters, reduce="min")


def _cases(smoke: bool):
    """name -> (BCSR, N).  Sizes are interpret-mode (CPU) friendly in smoke
    mode; the full suite mirrors the paper's structure classes at ~4-8x
    scaled-down sizes."""
    s = 1 if smoke else 4
    block = (16, 16)
    cases = []
    cases.append(("band", bcsr_lib.from_scipy(
        topology.band(256 * s, 8 * s), block), 128 * s))
    cases.append(("power_law_skew", bcsr_lib.from_scipy(
        topology.power_law(256 * s, 4.0, seed=3), block), 128 * s))
    cases.append(("uniform_p10", bcsr_lib.random_bcsr(
        0, (256 * s, 256 * s), block, 0.10), 128 * s))
    cases.append(("near_dense_p90", bcsr_lib.random_bcsr(
        1, (128 * s, 128 * s), block, 0.90), 128 * s))
    cases.append(("tall_skinny_n32", bcsr_lib.random_bcsr(
        2, (256 * s, 128 * s), block, 0.25), 32))
    return cases


def run(smoke: bool, cache_path=None) -> dict:
    tuner = autotune.Autotuner(cache_path=cache_path)
    iters = 5
    rows = []
    for name, a, n in _cases(smoke):
        a = a.ensure_nonempty_rows()
        fp = autotune.fingerprint_bcsr(a, n)
        choice, timings = tuner.tune(a, n, iters=iters, interpret=True)
        cached = tuner.get(fp)  # what backend="auto" dispatch will use
        tuned_label = f"{cached.variant}/bn{cached.bn}"
        # re-time default and the cached pick in a fresh pass (not the
        # sweep's own numbers) so a genuinely-slow pick fails the gate
        arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
        b = jnp.asarray(np.random.default_rng(0).standard_normal(
            (meta.shape[1], n)).astype(np.float32))
        default_s = _time_config(arrays, meta, b, autotune.DEFAULT_VARIANT,
                                 autotune.DEFAULT_BN, iters=iters)
        if (cached.variant, cached.bn) == (autotune.DEFAULT_VARIANT,
                                           autotune.DEFAULT_BN):
            tuned_s = default_s  # identical config — nothing to re-time
        else:
            tuned_s = _time_config(arrays, meta, b, cached.variant,
                                   cached.bn, iters=iters)
        speedup = (default_s / tuned_s) if (default_s and tuned_s) else 1.0
        row = {
            "name": name,
            "fingerprint": fp.key(),
            "choice": choice.to_dict(),
            "default_us": round(default_s * 1e6, 2) if default_s else None,
            "tuned_us": round(tuned_s * 1e6, 2) if tuned_s else None,
            "speedup_vs_default": round(speedup, 3),
            "timings_us": {k: v if isinstance(v, str) else round(v * 1e6, 2)
                           for k, v in timings.items()},
        }
        rows.append(row)
        print(f"{name:>18}: {tuned_label:<16} "
              f"{row['tuned_us']}us vs default {row['default_us']}us "
              f"({row['speedup_vs_default']}x)", file=sys.stderr)
    return {
        "bench": "autotune",
        "mode": "smoke" if smoke else "full",
        "min_speedup_gate": MIN_SPEEDUP_SMOKE if smoke else MIN_SPEEDUP,
        "cases": rows,
    }


def diff(result: dict, baseline: dict) -> int:
    """Regression diff: structural parity with the baseline + the
    never-slower-than-default gate.  Returns a process exit code."""
    got = {c["name"]: c for c in result["cases"]}
    want = {c["name"]: c for c in baseline["cases"]}
    gate = result.get("min_speedup_gate", MIN_SPEEDUP)
    failures = []
    for name in sorted(set(want) - set(got)):
        failures.append(f"case disappeared vs baseline: {name}")
    for name in sorted(set(got) - set(want)):
        print(f"note: new case not in baseline: {name}", file=sys.stderr)
    for name, c in got.items():
        sp = c["speedup_vs_default"]
        if sp < gate:
            failures.append(
                f"{name}: tuned pick {c['choice']['variant']}/"
                f"bn{c['choice']['bn']} is slower than the hardcoded "
                f"default ({sp}x < {gate}x gate)")
        base = want.get(name)
        if base and base["choice"]["variant"] != c["choice"]["variant"]:
            print(f"note: {name} choice changed "
                  f"{base['choice']['variant']} -> {c['choice']['variant']} "
                  "(machine-dependent; informational)", file=sys.stderr)
    if failures:
        print("AUTOTUNE REGRESSION:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"autotune diff OK: {len(got)} cases, all >= "
          f"{gate}x of default", file=sys.stderr)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small matrices / few iters (CI job)")
    ap.add_argument("--out", default="BENCH_autotune.json",
                    help="where to write the results JSON")
    ap.add_argument("--cache", default=None,
                    help="autotune decision cache JSON (persisted picks)")
    ap.add_argument("--diff", default=None, metavar="BASELINE",
                    help="after running, diff results against this baseline")
    args = ap.parse_args()

    result = run(args.smoke, cache_path=args.cache)
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
