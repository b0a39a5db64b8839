"""Useful operations of the window's prefill steps over the seconds the
device was busy in the traced window times the chip's bf16 peak, in %: the
whole step's share of its peak while the chip works, whatever the host
adds between steps (``device_idle.lib`` reads that part).  Operations per
step from the configuration's published sizes
(``chipbench/counts_models.py``): MLA projections, causal attention at
its true positions, the dense FFN, the router, the shared and the top-k
routed experts, and the head at the last position; no padding, capacity
or recomputation."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    busy_s = ctx.trace.busy_s()
    if busy_s <= 0:
        return None
    ops = ctx.setup["flops_per_step"] * ctx.window["calls"]
    return 100.0 * ops / busy_s / ctx.peak["bf16_flops_s"]
