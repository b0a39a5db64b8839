"""Readings of a cell's correctness check, for setting its limits.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5 [--variants program,control]

Runs the cell once per seed and variant in one process on the chip:
``program`` as the benchmark runs it, ``control`` with the plain reference
in the next lower precision in the program's place.  Prints one JSON line
per run with the numbers compared.  The benchmark's own runs never run the
control; its limits are set between the two readings.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from chipbench import bench, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--variants", default="control")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache(bench.CACHE)
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant in args.variants.split(","):
            opts = harness.Options(variant=variant)
            r = harness.run_cell(cell, seed, args.seconds, False,
                                 time.perf_counter(), opts)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": variant, "correct": r["correct"],
                              "checks": r["checks"],
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
