"""Retrace sentinel and compile counters: what jit traced and compiled.

The serving engine, the paged decode gather, and the sharded SpMM all
promise "never retraces" in comments; this module turns that into an
assertion.  :func:`monitor` wraps a function that jit (or grad / vmap /
scan) will trace; the wrapper bumps a named :class:`Sentinel` ONLY when
called with a traced argument (any pytree leaf is a ``jax.core.Tracer``)
— i.e. exactly once per (re)trace per call site, and never on eager calls
or jit cache hits.  ``assert_max_traces(target, n)`` then
raises :class:`RetraceError` when the count exceeds the budget.

Wrap the function BEFORE handing it to ``jax.jit`` (the engine does this
for ``_masked_step``), or decorate a function that is called from inside
traced code (``models.layers._paged_decode``,
``launch.dist_spmm.spmm_sharded``) — for the latter, the count is "times
the body was traced", so a function inlined L times per program counts L
per trace; budget accordingly.

Compile counters: on its first import this module registers
``jax.monitoring`` listeners, which add the seconds of each compile phase
of every jitted program to the gauge ``jax.compile.seconds{phase=...}``
(``trace``: tracing to a jaxpr; ``lower``: the jaxpr to an MLIR module;
``compile``: the backend compile; ``cache_load``: a load from the
persistent compilation cache, which JAX times inside its backend-compile
span, so it is taken out of ``compile``), and count ``jax.compiles``
(backend compiles) and ``jax.cache_hits`` (loads).  A jit traced inside
another's trace is counted once, inside its parent.  So the registry
says which step recompiled and what set-up spent compiling.

This module is the one ``repro.obs`` member that is trace-time-safe by
design (it only inspects argument types and mutates host counters), so lint R7
(``obs-host-only``) exempts it.  It imports jax, to register the listener.

>>> import jax, jax.numpy as jnp
>>> @monitor(name="doc.f")
... def f(x):
...     return x * 2
>>> g = jax.jit(f)
>>> _ = g(jnp.ones((4,))); _ = g(jnp.ones((4,)))   # one trace, one hit
>>> trace_count("doc.f")
1
>>> _ = g(jnp.ones((8,)))                          # new shape: retrace
>>> assert_max_traces("doc.f", 1)   # doctest: +IGNORE_EXCEPTION_DETAIL
Traceback (most recent call last):
    ...
RetraceError: doc.f: traced 2 times, budget 1
>>> reset("doc.f")
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional

import jax.monitoring

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


class RetraceError(AssertionError):
    """A monitored entry point traced more often than its budget."""


class Sentinel:
    __slots__ = ("name", "count", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self._lock = threading.Lock()

    def bump(self) -> int:
        with self._lock:
            self.count += 1
            return self.count

    def reset(self) -> None:
        with self._lock:
            self.count = 0

    def __repr__(self):
        return f"Sentinel({self.name!r}, count={self.count})"


_REGISTRY: Dict[str, Sentinel] = {}
_LOCK = threading.Lock()


def _trace_active(args, kwargs) -> bool:
    import jax
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves((args, kwargs)))


def monitor(fn=None, *, name: Optional[str] = None):
    """Decorator/wrapper installing a retrace sentinel on ``fn``.

    Registers the sentinel process-wide under ``name`` (default: the
    function's qualname; latest registration wins — each ``ServeEngine``
    re-registers ``serve.masked_step`` for its own closure).  The
    sentinel is also reachable as ``wrapped.sentinel``."""
    if fn is None:
        return functools.partial(monitor, name=name)
    s = Sentinel(name or getattr(fn, "__qualname__", repr(fn)))
    with _LOCK:
        _REGISTRY[s.name] = s

    @functools.wraps(fn)
    def wrapper(*a, **kw):
        if _trace_active(a, kw):
            n = s.bump()
            _trace.event("jax.trace", fn=s.name, n=n)
        return fn(*a, **kw)

    wrapper.sentinel = s
    return wrapper


def _resolve(target) -> Sentinel:
    if isinstance(target, Sentinel):
        return target
    if isinstance(target, str):
        s = _REGISTRY.get(target)
        if s is None:
            known = sorted(_REGISTRY)
            raise KeyError(f"no retrace sentinel named {target!r}; "
                           f"registered: {known}")
        return s
    s = getattr(target, "sentinel", None)
    if isinstance(s, Sentinel):
        return s
    raise TypeError(f"expected a sentinel name, a monitored function, or "
                    f"a Sentinel; got {target!r}")


def trace_count(target) -> int:
    """How many times the monitored body has been traced so far."""
    return _resolve(target).count


def assert_max_traces(target, n: int) -> None:
    """Raise :class:`RetraceError` when ``target`` traced more than ``n``
    times — the CI gate for static-shape promises."""
    s = _resolve(target)
    if s.count > n:
        raise RetraceError(
            f"{s.name}: traced {s.count} times, budget {n} — a "
            "static-shape promise broke (shape/dtype-polymorphic inputs "
            "reached a jitted entry point)")


def reset(target=None) -> None:
    """Zero one sentinel, or every registered sentinel (test isolation)."""
    if target is not None:
        _resolve(target).reset()
        return
    with _LOCK:
        for s in _REGISTRY.values():
            s.reset()


def sentinels() -> Dict[str, int]:
    """Snapshot ``{name: trace_count}`` of every registered sentinel."""
    with _LOCK:
        return {name: s.count for name, s in sorted(_REGISTRY.items())}


# ---------------------------------------------------------- compile counters
_SPAN_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_STACK_CAP = 1024              # spans kept per phase and thread for nesting
_compile_tls = threading.local()
_COMPILE_LOCK = threading.Lock()


def _add_seconds(phase: str, secs: float) -> None:
    with _COMPILE_LOCK:                    # threads may compile at once
        g = _metrics.gauge("jax.compile.seconds", phase=phase)
        g.set(g.value + secs)


def _on_span(event: str, start: float, end: float, **_) -> None:
    """One timed compile phase: nested spans of the same phase (a jit
    traced inside another's trace) are reported first and counted inside
    their parent's, so each second is counted once."""
    phase = _SPAN_PHASES.get(event)
    if phase is None:
        return
    stacks = getattr(_compile_tls, "stacks", None)
    if stacks is None:
        stacks = _compile_tls.stacks = {}
    stack = stacks.setdefault(phase, [])
    inner = 0.0
    while stack and stack[-1][0] >= start:
        s, e = stack.pop()
        inner += e - s
    stack.append((start, end))
    del stack[:-_STACK_CAP]
    secs = (end - start) - inner
    if phase == "compile":
        loaded = getattr(_compile_tls, "loaded", None)
        _compile_tls.loaded = None
        if loaded is None:
            _metrics.counter("jax.compiles").inc()
        else:
            secs -= loaded
    _add_seconds(phase, secs)


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event != _CACHE_LOAD:
        return
    _compile_tls.loaded = duration_secs   # inside the compile span now
    _metrics.counter("jax.cache_hits").inc()
    _add_seconds("cache_load", duration_secs)


jax.monitoring.register_event_time_span_listener(_on_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
