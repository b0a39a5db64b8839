"""Whole-stack observability (PR 10): structured tracing, a metrics
registry, and a retrace sentinel.

Host-side and deterministic-friendly by construction:

* ``repro.obs.trace``   — hierarchical spans + events, ring buffer,
  ``REPRO_TRACE=0/1/<jsonl-path>`` gating (zero-cost when off);
* ``repro.obs.metrics`` — process-wide counters/gauges/histograms with
  labeled series, ``snapshot()``/``reset()``, and the shared benchmark
  ``timeit`` loop;
* ``repro.obs.export``  — JSONL / summary-tree views with deterministic
  payloads split from report-only wall clock;
* ``repro.obs.jaxmon``  — retrace sentinel (``monitor`` +
  ``assert_max_traces``) turning "never retraces" comments into CI gates,
  and the compile counters (``jax.compile.seconds{phase=...}``).

While tracing is on, spans are mirrored into ``jax.profiler``
annotations, so a profiler trace shows them on the device ops' clock.
The obs core (``trace``, ``metrics``, ``export``) never imports jax
(``timeit`` imports it lazily, the span mirror uses it only once
imported); ``jaxmon`` imports it to register its compile listener.
Lint R7 (``analysis.lint_rules``) keeps every ``repro.obs`` call out of
custom_vjp/Pallas-traced code — ``jaxmon`` excepted, trace-aware by
design.
"""
from repro.obs import export, jaxmon, metrics, trace
from repro.obs.metrics import counter, gauge, histogram, snapshot, timeit
from repro.obs.trace import capture, enabled, event, span, spanned

__all__ = [
    "trace", "metrics", "export", "jaxmon",
    "span", "spanned", "event", "capture", "enabled",
    "counter", "gauge", "histogram", "snapshot", "timeit",
]
