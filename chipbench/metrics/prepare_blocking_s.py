"""Host seconds of the first stage of the program's preparation: blocking
the CSR matrix into BCSR (``bcsr.from_csr``, which ``from_scipy``
delegates to), as the program's own gauge
``prepare.seconds{stage=blocking}`` holds it."""


def read(ctx):
    from repro.obs import metrics
    return metrics.snapshot()["gauges"].get("prepare.seconds{stage=blocking}")
