"""CLI: render a JSONL trace as a span-tree summary.

    REPRO_TRACE=trace.jsonl python examples/quickstart.py
    python -m repro.obs.summary trace.jsonl
"""
from __future__ import annotations

import argparse
import sys

from repro.obs import export


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.summary", description=__doc__)
    ap.add_argument("trace", help="JSONL trace (REPRO_TRACE sink or "
                                  "export.to_jsonl output)")
    args = ap.parse_args(argv)
    events = export.read_jsonl(args.trace)
    print(export.summary_tree(events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
