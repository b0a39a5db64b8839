"""Host seconds of the program's row reorder (``permute_bcsr``: the round
trip to CSR, the clustering, the re-blocking), as the program's own gauge
``prepare.seconds{stage=reorder}`` holds it."""


def read(ctx):
    from repro.obs import metrics
    return metrics.snapshot()["gauges"].get("prepare.seconds{stage=reorder}")
