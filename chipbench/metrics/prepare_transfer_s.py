"""Host seconds of the last stage of the program's preparation: the
prepared arrays put on the device and waited for, as the program's own
gauge ``prepare.seconds{stage=to_device}`` holds it."""


def read(ctx):
    from repro.obs import metrics
    return metrics.snapshot()["gauges"].get(
        "prepare.seconds{stage=to_device}")
