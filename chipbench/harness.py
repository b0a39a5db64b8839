"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is the whole run after the platform check, so the tests can
drive it on the CPU at a small size.  Its order is fixed:

1. the traffic driver's set-up (inputs from the seed, the program's
   own preparation, warm-up of every shape the window uses); the time from
   process start to here is ``setup_s``;
2. the window, ``seconds`` long, traced when ``trace`` is set; the driver
   waits for all its work before the window closes;
3. the peak device memory, then the program's state is freed;
4. the check against the plain reference (``correct``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from chipbench import bench, peaks, tracing

TRACE_DIR = "traces"


@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes at or below it."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Options:
    interpret: bool = False       # Pallas interpreter (the CPU tests)
    variant: str = "program"      # "control": the reference in lower
                                  # precision takes the program's place
    cache_dir: Path = bench.CACHE


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader reads."""
    cell: bench.Cell
    trace: Optional[tracing.Trace]
    peak: Optional[dict]
    setup: dict                    # the traffic driver's set-up record
    window: dict                   # the traffic driver's window record


def enable_compile_cache(cache_dir: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however quickly it compiles."""
    import jax
    path = str(Path(cache_dir) / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the benchmark's spans and the runtime
    return opts


def run_cell(cell: bench.Cell, seed: int, seconds: float, trace: bool,
             t0: float, opts: Options = Options()) -> dict:
    """Run ``cell`` once; returns the result line as a dict."""
    import jax
    phases = {}                  # host seconds of each part of the run
    driver = bench.driver(cell)
    gc.collect()                 # what an earlier run in this process left
    state = driver.setup(cell, seed, opts)
    setup_s = time.perf_counter() - t0
    # what set-up made lives through the window: keep the collector from
    # walking it again and again there
    gc.collect()
    gc.freeze()

    trace_dir = Path(opts.cache_dir) / TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=_trace_options())
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            window = driver.window(state, seconds)
        phases["window_s"] = time.perf_counter() - t
    finally:
        if trace:
            jax.profiler.stop_trace()
        gc.unfreeze()

    device = device_record(cell.chips)
    driver.free(state)
    gc.collect()
    t = time.perf_counter()
    checks: List[Check] = driver.check(state, window, opts)
    phases["check_s"] = time.perf_counter() - t

    result: Dict[str, object] = {
        "correct": all(c.ok for c in checks),
        "attempted": window["attempted"],
        "failed": window["failed"],
    }
    if trace:
        t = time.perf_counter()
        tr = tracing.load(trace_dir)
        peak = peaks.peaks(device["kind"]) if device["platform"] == "tpu" \
            else None
        ctx = Context(cell=cell, trace=tr, peak=peak, setup=state.record,
                      window=window)
        values = bench.read_metrics(cell, ctx)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items()}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["device"] = device
        result["breakdown"] = tracing.breakdown(tr)
        phases["trace_read_s"] = time.perf_counter() - t
    else:
        values = dict(driver.end_to_end(state, window))
        values["setup_s"] = setup_s
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {c.name: {"value": _number(c.value), "limit": c.limit}
                        for c in checks}
    phases["setup_s"] = setup_s
    print("chipbench: " + json.dumps({k: round(v, 3) for k, v in
                                       phases.items()}),
          file=sys.stderr, flush=True)
    return result


def _number(x: float):
    """A JSON number, or None for a value that is not finite."""
    return float(x) if math.isfinite(x) else None


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, and the result as the last line on standard output."""
    for name, c in result["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        verdict = "ok" if ok else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
