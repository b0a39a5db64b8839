"""Operations and bytes a problem needs, computed from its shapes.

These counts are the yardstick's own: they do not depend on how the
program blocks, reorders or pads its operands, so a change to any of
those is judged by the same count.
"""
from __future__ import annotations


def spmm_counts(*, nnz: int, n_rows: int, n_cols: int, n: int,
                val_bytes: int, io_bytes: int, index_bytes: int):
    """``(operations, bytes)`` of ``C[n_rows, n] = A @ B[n_cols, n]``.

    Operations: ``2 * nnz * n`` (a multiply and an add per nonzero and
    column of B).  Bytes: each nonzero's value once, the index bytes of the
    format the matrix is given in (``index_bytes``), B read once and C
    written once."""
    ops = 2 * nnz * n
    nbytes = (nnz * val_bytes + index_bytes +
              n_cols * n * io_bytes + n_rows * n * io_bytes)
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(ops / peak["bf16_flops_s"], nbytes / peak["hbm_bytes_s"])

