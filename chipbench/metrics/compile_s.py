"""Host seconds the run's process spent compiling, by the program's own
gauges ``jax.compile.seconds{phase=trace|lower|compile|cache_load}``
(``repro.obs.jaxmon``), summed when the metric is read: after the window
and the check, so the check's compiles count too."""

PREFIX = "jax.compile.seconds{"


def read(ctx):
    from repro.obs import metrics
    secs = [v for k, v in metrics.snapshot()["gauges"].items()
            if k.startswith(PREFIX)]
    return sum(secs) if secs else None
