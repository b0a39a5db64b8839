"""Share of the stored block entries that hold a nonzero of the matrix
after the program's reorder and blocking: ``nnz / (nnzb * h * w)``, in %.
Exact: counted from the matrix and the program's prepared structure."""


def read(ctx):
    fill = ctx.setup.get("block_fill")
    return None if fill is None else 100.0 * fill
