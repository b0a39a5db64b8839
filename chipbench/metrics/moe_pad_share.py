"""Rows that pad an expert's last 128-row block, over the routed (token,
expert) rows, in %, summed over the window's steps and MoE layers: the
program's counters ``moe.rows{kind=padding}`` and ``moe.rows{kind=routed}``
(``repro.models.moe.record_stats``), set from the device's routing by the
benchmark's driver after each step."""

ROUTED = "moe.rows{kind=routed}"
PADDING = "moe.rows{kind=padding}"


def read(ctx):
    from repro.obs import metrics
    counters = metrics.snapshot()["counters"]
    routed = counters.get(ROUTED)
    if not routed or PADDING not in counters:
        return None
    return 100.0 * counters[PADDING] / routed
