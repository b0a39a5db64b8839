"""Host seconds of the program's structure stage of preparation (row
padding, the transpose structure, the inverse permutation and the
dispatch stats), as the program's own gauge ``prepare.seconds{stage=meta}``
holds it."""


def read(ctx):
    from repro.obs import metrics
    return metrics.snapshot()["gauges"].get("prepare.seconds{stage=meta}")
