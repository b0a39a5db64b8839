"""Device time of the traced window's operations by the ``jax.named_scope``
parts of their ``op_name`` (``chipbench/xplane_ops.py``), for the model
cells' per-layer metrics."""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Tuple

from chipbench import xplane_ops

# one parse of a trace file serves every reader of a run
_op_names = functools.lru_cache(maxsize=2)(xplane_ops.op_names)


def scoped_ops(ctx) -> Optional[List[Tuple[float, str, str]]]:
    """``(device ns, HLO text, op_name)`` of each operation event of the
    window, or None when there is no trace to read."""
    if ctx.trace is None or not ctx.window.get("calls"):
        return None
    path = xplane_ops.trace_file(ctx.cell.name)
    if path is None:
        return None
    names = _op_names(path)
    return [(e - s, text, names.get(text, ""))
            for s, e, text in ctx.trace.ops()]


def ms_per_call(ctx, part: Callable[[str], bool]) -> Optional[float]:
    """Device milliseconds per call of the window's operations with a
    scope part for which ``part`` holds; None when there is no trace, or
    no operation has such a part (a program without the scope)."""
    ops = scoped_ops(ctx)
    if ops is None:
        return None
    ns = [d for d, _, op in ops if any(part(p) for p in op.split("/"))]
    if not ns:
        return None
    return sum(ns) * 1e-6 / ctx.window["calls"]
