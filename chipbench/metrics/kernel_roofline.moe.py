"""Share of their roofline that the routed experts' kernels reach, in %.

Least time of one step = max(operations / peak FLOP/s, bytes / peak HBM
bytes/s) (``chipbench/counts.py``), with the operations and bytes the
routed experts need whatever the blocking (``chipbench/counts_models.py``):

* operations ``2 * 3 * D * F`` per routed (token, expert) pair;
* bytes: each MoE layer's expert weights read once, and each routed row
  read in and written out once.

Kernel time is the summed device time of the Pallas kernels
(``tpu_custom_call``) under the program's ``smat.moe.experts`` scope in
the traced window: the gate and up SDDMMs and the down SpMM.  The share
is least time times the window's steps over that sum; with no such
kernel in the trace there is nothing to read.
"""
from chipbench import counts, op_scopes

KERNEL_PATTERN = 'custom_call_target="tpu_custom_call"'
SCOPE = "smat.moe.experts"


def read(ctx):
    if ctx.peak is None:
        return None
    ops = op_scopes.scoped_ops(ctx)
    if ops is None:
        return None
    kernel_s = sum(d for d, text, op in ops if KERNEL_PATTERN in text
                   and SCOPE in op.split("/")) * 1e-9
    if kernel_s <= 0:
        return None
    moe = ctx.setup["moe"]
    least = counts.least_time_s(moe["ops"], moe["bytes"], ctx.peak)
    return 100.0 * least * ctx.window["calls"] / kernel_s
