"""Plain SDDMM at the nonzeros of a CSR matrix: ``v[n] = x[i_n] . y[j_n]``
for its nonzero ``(i_n, j_n)``, in the CSR's own order, every product
and sum in float32.

Nonzeros are taken in row blocks of about ``ELEMENTS`` gathered products,
so the reference fits beside the program's outputs.  ``precision="fp8"``
is the control: X and Y rounded to float8 (e4m3) first, the step below
the bfloat16 operands the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROUNDING = {"f32": None, "fp8": jnp.float8_e4m3fn}
ELEMENTS = 2**25


def _round(x, precision: str):
    dt = ROUNDING[precision]
    x = jnp.asarray(x, jnp.float32)
    return x if dt is None else x.astype(dt).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("length",))
def _values(rows, cols, x, y, start, *, length):
    r = jax.lax.dynamic_slice(rows, (start,), (length,))
    c = jax.lax.dynamic_slice(cols, (start,), (length,))
    return jnp.sum(x[r] * y[c], axis=-1)


class SddmmReference:
    """The nonzeros ``(i_n, j_n)`` of a CSR as device arrays, padded so
    every block's slice is in range; ``n`` is the width of X and Y, and a
    block gathers about ``elements`` products."""

    def __init__(self, indptr, indices, shape, n: int,
                 elements: int = ELEMENTS):
        indptr = np.asarray(indptr)
        self.nnz = int(indptr[-1])
        self.length = int(max(1, min(self.nnz, elements // max(n, 1))))
        self.starts = list(range(0, self.nnz, self.length))
        rows = np.repeat(np.arange(shape[0], dtype=np.int32),
                         np.diff(indptr).astype(np.int64))
        pad = self.length
        self.rows = jnp.asarray(np.concatenate([rows, np.zeros(pad,
                                                                np.int32)]))
        self.cols = jnp.asarray(np.concatenate(
            [np.asarray(indices, np.int32), np.zeros(pad, np.int32)]))

    def blocks(self, x, y, precision: str = "f32"):
        """Yields ``(n0, n1, v[n0:n1])`` for every block of nonzeros."""
        x, y = _round(x, precision), _round(y, precision)
        for s in self.starts:
            v = _values(self.rows, self.cols, x, y, s, length=self.length)
            e = min(s + self.length, self.nnz)
            yield s, e, v[: e - s]


def _gap(pairs) -> float:
    """``max |o - v| / max |v|`` over ``(o, v)`` pairs of blocks."""
    err = scale = jnp.float32(0)
    for o, v in pairs:
        err = jnp.maximum(err, jnp.max(jnp.abs(o - v)))
        scale = jnp.maximum(scale, jnp.max(jnp.abs(v)))
    err, scale = float(err), float(scale)
    return err / scale if scale else float("inf")


def rel_err(ref: SddmmReference, x, y, values) -> float:
    """``max |values - v| / max |v|``; ``values`` [nnz] are the program's
    in the CSR's order."""
    return _gap((jnp.asarray(values[s:e], jnp.float32), v)
                for s, e, v in ref.blocks(x, y))


def control_rel_err(ref: SddmmReference, x, y, out_dtype) -> float:
    """The control's reading: the fp8 values, returned in the program's
    output dtype, against the float32 ones."""
    ctrl = {s: v for s, _, v in ref.blocks(x, y, "fp8")}
    return _gap((ctrl[s].astype(out_dtype).astype(jnp.float32), v)
                for s, _, v in ref.blocks(x, y))
