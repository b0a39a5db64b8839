"""Share of its roofline that the library op's kernels reach, in %.

Least time of one call = max(operations / peak FLOP/s, bytes / peak HBM
bytes/s) (``chipbench/counts.py``), with the operations and bytes the
problem needs whatever the blocking:

* operations ``2 * nnz * N``, nnz the matrix's true nonzeros;
* bytes: each nonzero's value at the operand dtype and its 4-byte column
  index (the matrix is given in CSR), B read once, C written once.

Kernel time is the summed device time of the op's kernel events in the
traced window: every Pallas kernel the op launches, matched by
``KERNEL_PATTERN`` in the operation's HLO text.  The share is least time
times the calls of the window over that sum; with no kernel event in the
trace there is nothing to read.
"""
from chipbench import counts

KERNEL_PATTERN = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    kernel_s = sum(e - s for s, e, _ in ctx.trace.ops(KERNEL_PATTERN)) * 1e-9
    if kernel_s <= 0:
        return None
    rec = ctx.setup
    m, k = rec["shape"]
    ops, nbytes = counts.spmm_counts(
        nnz=rec["nnz"], n_rows=m, n_cols=k, n=rec["n"],
        val_bytes=rec["val_bytes"], io_bytes=rec["io_bytes"],
        index_bytes=4 * rec["nnz"])
    least = counts.least_time_s(ops, nbytes, ctx.peak)
    return 100.0 * least * ctx.window["calls"] / kernel_s
