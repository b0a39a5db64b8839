"""Plain SpMM from the CSR: ``C[r] = sum over the row's nonzeros of
A[r, c] * B[c]``, every product and sum in float32.

Rows are taken in blocks of about ``ELEMENTS`` gathered products, so the
reference fits beside the program's outputs.  ``precision="fp8"`` is the
control: A and B rounded to float8 (e4m3) first, the step below the
bfloat16 operands the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROUNDING = {"f32": None, "fp8": jnp.float8_e4m3fn}
ELEMENTS = 2**25


@functools.partial(jax.jit, static_argnames=("n_rows", "length"))
def _rows(rows, cols, data, b, start, r0, *, n_rows, length):
    r = jax.lax.dynamic_slice(rows, (start,), (length,)) - r0
    c = jax.lax.dynamic_slice(cols, (start,), (length,))
    v = jax.lax.dynamic_slice(data, (start,), (length,))
    r = jnp.where((r >= 0) & (r < n_rows), r, n_rows)   # padding: dropped
    return jax.ops.segment_sum(v[:, None] * b[c], r, num_segments=n_rows,
                               indices_are_sorted=True)


def _round(x, precision: str):
    dt = ROUNDING[precision]
    return x if dt is None else x.astype(dt).astype(jnp.float32)


class SpmmReference:
    """``A`` held as device COO triplets, padded so every row block's slice
    is in range.  ``values`` are the float32 values the program was given,
    rounded to the operand dtype it computes in; ``n`` is the width of B,
    and a row block gathers about ``elements`` products."""

    def __init__(self, indptr, indices, values, shape, dtype, n: int,
                 elements: int = ELEMENTS):
        self.indptr = np.asarray(indptr)
        self.shape = tuple(shape)
        m = self.shape[0]
        per_row = max(self.indptr[-1] / max(m, 1), 1.0)
        self.block_rows = int(min(m, max(1, elements // (n * per_row))))
        self.starts = list(range(0, m, self.block_rows))
        self.ends = [min(s + self.block_rows, m) for s in self.starts]
        self.length = max(int(self.indptr[e] - self.indptr[s])
                          for s, e in zip(self.starts, self.ends))
        rows = np.repeat(np.arange(m, dtype=np.int32),
                         np.diff(self.indptr).astype(np.int64))
        pad = self.length
        self.rows = jnp.asarray(np.concatenate(
            [rows, np.full(pad, -1, np.int32)]))
        self.cols = jnp.asarray(np.concatenate(
            [np.asarray(indices, np.int32), np.zeros(pad, np.int32)]))
        vals = jnp.asarray(np.asarray(values, np.float32)).astype(
            dtype).astype(jnp.float32)
        self.data = jnp.concatenate([vals, jnp.zeros(pad, jnp.float32)])

    def blocks(self, b, precision: str = "f32"):
        """Yields ``(r0, r1, C[r0:r1])`` for every row block."""
        data = _round(self.data, precision)
        b = _round(jnp.asarray(b, jnp.float32), precision)
        for r0, r1 in zip(self.starts, self.ends):
            c = _rows(self.rows, self.cols, data, b, int(self.indptr[r0]),
                      r0, n_rows=self.block_rows, length=self.length)
            yield r0, r1, c[: r1 - r0]


def _gap(pairs) -> float:
    """``max |o - c| / max |c|`` over ``(o, c)`` pairs of row blocks."""
    err = scale = jnp.float32(0)
    for o, c in pairs:
        err = jnp.maximum(err, jnp.max(jnp.abs(o - c)))
        scale = jnp.maximum(scale, jnp.max(jnp.abs(c)))
    err, scale = float(err), float(scale)
    return err / scale if scale else float("inf")


def rel_err(ref: SpmmReference, b, out) -> float:
    """``max |out - C| / max |C|`` over the whole product."""
    return _gap((jnp.asarray(out[r0:r1], jnp.float32), c)
                for r0, r1, c in ref.blocks(b))


def control_rel_err(ref: SpmmReference, b, out_dtype) -> float:
    """The control's reading: the fp8 product, returned in the program's
    output dtype, against the float32 one."""
    ctrl = dict((r0, c) for r0, _, c in ref.blocks(b, "fp8"))
    return _gap((ctrl[r0].astype(out_dtype).astype(jnp.float32), c)
                for r0, _, c in ref.blocks(b))
