"""Each device operation's ``op_name``, read from a profiler trace.

``jax.profiler.ProfileData``, which ``chipbench/tracing.py`` reads, names
an operation event by its HLO text but does not give the stats of the
event's metadata.  The runtime keeps the operation's ``op_name`` there,
in the ``tf_op`` stat: the path of the ``jax.named_scope`` names it was
traced under, then its primitive, as in
``jit(<lambda>)/smat.epilogue/jit(_take)/gather``.  This module reads
that stat with a small walk over the protobuf wire format of the
``.xplane.pb`` (``XSpace`` of ``tsl/profiler/protobuf/xplane.proto``), so
it needs no generated protobuf classes, and joins it with the events of
``Trace.ops()``.

Only the messages on the path are decoded: ``XSpace.planes`` (1), and of
each device plane its name (2), ``event_metadata`` (4, a map of id to
``XEventMetadata``: name 2, stats 5) and ``stat_metadata`` (5, a map of
id to ``XStatMetadata``: name 2).  A stat (``XStat``) names its kind by
``metadata_id`` (1) and holds a string in ``str_value`` (5) or, interned,
in ``ref_value`` (7), the id of a ``stat_metadata`` entry whose name is
the string.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple

from chipbench import bench, harness, tracing

TF_OP = "tf_op"
SCOPE_PREFIX = "smat."


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of one message: an int for
    a varint, a memoryview for a length-delimited field, None for the
    fixed-width ones, which nothing here reads."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _map_values(plane: memoryview, field: int) -> Iterator[memoryview]:
    """The value message of each entry of the map field ``field``."""
    for f, entry in _fields(plane):
        if f == field:
            for k, value in _fields(entry):
                if k == 2:
                    yield value


def _plane_op_names(plane: memoryview) -> Dict[str, str]:
    stat_names = {}
    for meta in _map_values(plane, 5):
        d = dict(_fields(meta))
        stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
    tf_op = [i for i, n in stat_names.items() if n == TF_OP]
    out: Dict[str, str] = {}
    if not tf_op:
        return out
    for meta in _map_values(plane, 4):
        name, op = None, None
        for f, value in _fields(meta):
            if f == 2:
                name = bytes(value).decode()
            elif f == 5:
                stat = dict(_fields(value))
                if stat.get(1) != tf_op[0]:
                    continue
                if 5 in stat:
                    op = bytes(stat[5]).decode()
                elif 7 in stat:
                    op = stat_names.get(stat[7], "")
        if name is not None and op:
            # the stat reads "<op_name>:<op_type>"; the type is empty here
            out.setdefault(name, op.rsplit(":", 1)[0] if ":" in op else op)
    return out


def op_names(path) -> Dict[str, str]:
    """``{HLO text: op_name}`` of the operations of every device plane of
    the ``.xplane.pb`` at ``path``; the HLO text is what
    ``Trace.ops()`` names an event by."""
    buf = memoryview(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane) if k == 2),
                    "")
        if name.startswith(tracing.DEVICE_PREFIX):
            for text, op in _plane_op_names(plane).items():
                out.setdefault(text, op)
    return out


def trace_file(cell_name: str) -> Optional[Path]:
    """The ``.xplane.pb`` the harness wrote for the cell's traced window,
    or None."""
    root = bench.CACHE / harness.TRACE_DIR / cell_name
    found = sorted(root.glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def under(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies under the scope ``scope``: one of its
    ``/``-separated parts is ``scope``."""
    return scope in op_name.split("/")


def scope_ms_per_call(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per call of the window's operations under
    ``scope``; None when there is no trace to read, or no operation of
    it carries an ``smat.`` scope (a program without them)."""
    if ctx.trace is None or not ctx.window.get("calls"):
        return None
    path = trace_file(ctx.cell.name)
    if path is None:
        return None
    names = op_names(path)
    ops = [(e - s, names.get(text, "")) for s, e, text in ctx.trace.ops()]
    if not any(p.startswith(SCOPE_PREFIX)
               for _, op in ops for p in op.split("/")):
        return None
    ns = sum(d for d, op in ops if under(op, scope))
    return ns * 1e-6 / ctx.window["calls"]
