"""Share of its roofline that the SDDMM op's kernels reach, in %.

Least time of one call = max(operations / peak FLOP/s, bytes / peak HBM
bytes/s) (``chipbench/counts.py``), with the operations and bytes the
problem needs whatever the blocking (``chipbench/counts_models.py``):

* operations ``2 * nnz * N``, nnz the matrix's true nonzeros;
* bytes: X and Y read once, each nonzero's value written once at the
  output dtype with a 4-byte index.

Kernel time is the summed device time of the op's Pallas kernels
(``tpu_custom_call``) in the traced window.  The share is least time
times the window's calls over that sum; with no kernel event in the trace
there is nothing to read.
"""
from chipbench import counts, counts_models

KERNEL_PATTERN = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    kernel_s = sum(e - s for s, e, _ in ctx.trace.ops(KERNEL_PATTERN)) * 1e-9
    if kernel_s <= 0:
        return None
    rec = ctx.setup
    m, k = rec["shape"]
    ops, nbytes = counts_models.sddmm_counts(
        nnz=rec["nnz"], n_rows=m, n_cols=k, n=rec["n"],
        io_bytes=rec["io_bytes"], out_bytes=rec["out_bytes"])
    least = counts.least_time_s(ops, nbytes, ctx.peak)
    return 100.0 * least * ctx.window["calls"] / kernel_s
