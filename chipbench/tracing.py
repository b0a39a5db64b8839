"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The device planes (``/device:TPU:<i>``) hold one line of program
executions (``XLA Modules``) and one of operations (``XLA Ops``, each named
by its HLO text).  The host plane holds the benchmark's own spans
(``TraceAnnotation`` names starting ``bench.``) and the runtime's events.
The device clock is shifted onto the host's by the median distance from
each program launch on the host to its execution on device 0.

Read with ``jax.profiler.ProfileData`` alone; nothing here touches a
device.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"

Event = Tuple[float, float, str]        # (start_ns, end_ns, name)


@dataclasses.dataclass
class DeviceLines:
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]          # host clock, ns
    devices: Dict[str, DeviceLines]      # on the host clock, in the window
    spans: List[Event]                   # the benchmark's spans
    runtime: List[Event]                 # other host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return statistics.fmean(
            union_ns(d.ops) for d in self.devices.values()) * 1e-9

    def ops(self, pattern: Optional[str] = None) -> List[Event]:
        """Operation events of every device whose HLO text holds
        ``pattern`` (all of them for None)."""
        return [e for d in self.devices.values() for e in d.ops
                if pattern is None or pattern in e[2]]

    def modules(self) -> List[Event]:
        return [e for d in self.devices.values() for e in d.modules]


def union_ns(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for s, e, _ in sorted(events):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _merged(events: Sequence[Event]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line) -> List[Event]:
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def load(path) -> Trace:
    """Read the trace at ``path`` (an ``.xplane.pb``, or a directory that
    holds one under ``plugins/profile/``)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    runtime: List[Event] = []
    launches: List[float] = []
    devices: Dict[str, DeviceLines] = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in _events(line):
                    if ev[2].startswith(SPAN_PREFIX):
                        spans.append(ev)
                    else:
                        runtime.append(ev)
                    if ev[2] == LAUNCH_EVENT:
                        launches.append(ev[0])
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = DeviceLines(
                ops=lines.get("XLA Ops", []),
                modules=lines.get("XLA Modules", []))
    windows = [e for e in spans if e[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0][0], windows[0][1]
    shift = _device_shift(sorted(launches), devices)
    for name, d in devices.items():
        devices[name] = DeviceLines(
            ops=_clip(_shifted(d.ops, shift), lo, hi),
            modules=_clip(_shifted(d.modules, shift), lo, hi))
    return Trace(window=(lo, hi), devices=devices,
                 spans=sorted(spans), runtime=sorted(runtime))


def _shifted(events: Sequence[Event], shift: float) -> List[Event]:
    return [(s + shift, e + shift, n) for s, e, n in events]


def _device_shift(launches: List[float],
                  devices: Dict[str, DeviceLines]) -> float:
    """Host-clock minus device-clock offset: the median distance from the
    i-th program launch on the host to the i-th execution on the first
    device (executions start after their launch, so the true offset is at
    least this)."""
    if not devices or not launches:
        return 0.0
    first = devices[sorted(devices)[0]].modules
    starts = sorted(s for s, _, _ in first)
    pairs = list(zip(launches, starts))
    if not pairs:
        return 0.0
    return statistics.median(h - d for h, d in pairs)


_OPCODE = re.compile(r"[\}\)\]]\s+([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(hlo_text: str) -> str:
    """Short name of an operation event: its instruction name and opcode
    (with the target of a custom call)."""
    name = hlo_text.split(" = ", 1)[0]
    target = _TARGET.search(hlo_text)
    if target:
        return f"{name} custom-call {target.group(1)}"
    op = _OPCODE.search(hlo_text)
    return f"{name} {op.group(1)}" if op else name


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """``(name, self ns)`` per event: its duration less that of the events
    nested inside it on the same line (a loop holds its body's ops)."""
    out = []
    stack: List[List] = []                  # [end, name, child ns]
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            end, name, child, start = stack.pop()
            out.append((name, (end - start) - child))
        if stack:
            stack[-1][2] += e - s
        stack.append([e, n, 0.0, s])
    while stack:
        end, name, child, start = stack.pop()
        out.append((name, (end - start) - child))
    return out


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` operations that took most device time (self time, summed
    over executions, averaged over devices), in seconds."""
    totals: Dict[str, float] = {}
    for d in trace.devices.values():
        for name, ns in self_times(d.ops):
            label = op_label(name)
            totals[label] = totals.get(label, 0.0) + ns
    n = max(len(trace.devices), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / n] for name, ns in ranked]


def _innermost(events: Sequence[Event], t: float) -> Optional[str]:
    best = None
    for s, e, n in events:
        if s <= t < e and (best is None or s >= best[0]):
            best = (s, n)
    return best[1] if best else None


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest stretches of the window in which the first device
    ran no operation, each named by what the host was doing in its middle:
    the innermost benchmark span, and the runtime event if one was open."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]].ops
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in _merged(ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        span = _innermost(trace.spans, mid) or "outside any span"
        rt = _innermost(trace.runtime, mid)
        out.append([f"{span} / {rt}" if rt else span, (e - s) * 1e-9])
    return out


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
