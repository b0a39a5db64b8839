"""SDDMM benchmark: the ``ops.sddmm`` variant family over attention-mask
structures.

For each case (a block-sparse attention mask pattern at a given sequence
length, plus one weight-gradient shape), runs the ``op="sddmm"`` autotune
micro-sweep and reports the measured winner against the hardcoded default
(``sddmm_stream``, bn=512).  Emits ``BENCH_sddmm.json`` for the CI
regression-diff step:

  python benchmarks/bench_sddmm.py --smoke --out BENCH_sddmm.json \
      --diff benchmarks/BENCH_sddmm.baseline.json

Gate policy (README ## Benchmarks): the DETERMINISTIC fields gate hard —
case set, mask nnzb / max_bpr (the mask builders are pure functions), the
v7 ``op=sddmm`` fingerprint key, and pick membership in the SDDMM variant
family.  Wall-clock numbers (speedup_vs_default, timings) are REPORT-ONLY:
interpret-mode timings on shared runners are not falsifiable.  Refresh
with ``--out benchmarks/BENCH_sddmm.baseline.json``.
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
from repro.kernels import autotune, ops
from repro.models import attention as A
from repro.obs import metrics as obs_metrics


def _cases(smoke: bool):
    """(name, host BCSR, n) — n is the SDDMM contraction width (the head
    dim for attention scores, the token count for weight gradients)."""
    seq = 256 if smoke else 1024
    blk = (16, 16)
    yield ("attn_banded",
           A.attention_mask_bcsr(A.banded(seq // 4), seq, blk), 64)
    yield ("attn_local_global",
           A.attention_mask_bcsr(A.local_global(seq // 8, seq // 16),
                                 seq, blk), 64)
    yield ("attn_causal",
           A.attention_mask_bcsr(A.blockwise_causal(), seq, blk), 64)
    # the dW shape: sparse weight structure, token-count contraction
    w = bcsr_lib.random_bcsr_exact(3, (seq, seq), blk,
                                   nnzb=max(2 * (seq // 16), 32))
    yield ("weight_grad", w, 128 if smoke else 512)


def _time_config(arrays, meta, x, y, variant, bn, iters=3):
    """Independent re-timing of one (variant, bn) config — not the sweep's
    own numbers, so a genuinely slow cached pick is visible here."""
    backend = autotune.get_variant(variant).backend
    fn = jax.jit(lambda xx, yy: ops.sddmm(arrays, meta, xx, yy,
                                          backend=backend, bn=bn,
                                          interpret=True))
    return obs_metrics.timeit(fn, x, y, warmup=1, iters=iters,
                              reduce="median")


def run(smoke: bool = True, cache_path=None) -> dict:
    tuner = autotune.Autotuner(cache_path=cache_path)
    rows = []
    for name, a, n in _cases(smoke):
        a = a.ensure_nonempty_rows()
        fp = autotune.fingerprint_bcsr(a, n, op="sddmm")
        choice, timings = tuner.tune(a, n, op="sddmm", iters=3,
                                     interpret=True)
        cached = tuner.get(fp)
        arrays, meta = ops.prepare_sparse(a, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((meta.shape[0], n)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((meta.shape[1], n)), jnp.float32)
        dv = autotune.default_variant("sddmm")
        default_s = _time_config(arrays, meta, x, y, dv,
                                 autotune.DEFAULT_BN)
        if (cached.variant, cached.bn) == (dv, autotune.DEFAULT_BN):
            tuned_s = default_s
        else:
            tuned_s = _time_config(arrays, meta, x, y, cached.variant,
                                   cached.bn)
        speedup = (default_s / tuned_s) if (default_s and tuned_s) else 1.0
        row = {
            "name": name,
            "fingerprint": fp.key(),
            "nnzb": meta.nnzb,
            "max_bpr": meta.max_bpr,
            "choice": cached.to_dict(),
            "default_us": round(default_s * 1e6, 2),
            "tuned_us": round(tuned_s * 1e6, 2),
            "speedup_vs_default": round(speedup, 3),
            "timings_us": {k: v if isinstance(v, str) else round(v * 1e6, 2)
                           for k, v in timings.items()},
        }
        rows.append(row)
        print(f"{name:>18}: {cached.variant}/bn{cached.bn} "
              f"{row['tuned_us']}us vs default {row['default_us']}us "
              f"({row['speedup_vs_default']}x)", file=sys.stderr)
    return {"bench": "sddmm", "mode": "smoke" if smoke else "full",
            "cases": rows}


def diff(result: dict, baseline: dict) -> int:
    """Regression diff.  Hard gates are the deterministic fields; timings
    are report-only (README ## Benchmarks policy)."""
    got = {c["name"]: c for c in result["cases"]}
    want = {c["name"]: c for c in baseline["cases"]}
    sddmm_family = set(autotune.variant_names("sddmm"))
    failures = []
    for name in sorted(set(want) - set(got)):
        failures.append(f"case disappeared vs baseline: {name}")
    for name, c in got.items():
        if not c["fingerprint"].startswith("v7|op=sddmm|"):
            failures.append(f"{name}: fingerprint not in the v7 op=sddmm "
                            f"key space: {c['fingerprint']}")
        if c["choice"]["variant"] not in sddmm_family:
            failures.append(f"{name}: pick {c['choice']['variant']!r} is "
                            f"not an SDDMM-family variant {sddmm_family}")
        base = want.get(name)
        if base is None:
            print(f"note: new case not in baseline: {name}", file=sys.stderr)
            continue
        for field in ("nnzb", "max_bpr", "fingerprint"):
            if base[field] != c[field]:
                failures.append(f"{name}: deterministic field {field!r} "
                                f"changed {base[field]} -> {c[field]}")
        if base["choice"]["variant"] != c["choice"]["variant"]:
            print(f"note: {name} choice changed "
                  f"{base['choice']['variant']} -> {c['choice']['variant']} "
                  "(machine-dependent; informational)", file=sys.stderr)
    if failures:
        print("SDDMM REGRESSION:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"sddmm diff OK: {len(got)} cases, deterministic fields stable",
          file=sys.stderr)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--diff", default=None)
    args = ap.parse_args()
    result = run(smoke=args.smoke)
    if args.out:
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
