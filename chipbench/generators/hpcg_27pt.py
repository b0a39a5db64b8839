"""HPCG's problem matrix: the 27-point stencil on an ``nx x ny x nz`` grid.

As the HPCG reference code builds it (``GenerateProblem_ref.cpp``), on one
process: grid point ``(ix, iy, iz)`` is row ``iz*nx*ny + iy*nx + ix``; its
row holds every neighbour ``(ix+sx, iy+sy, iz+sz)``, ``sx, sy, sz`` in
``{-1, 0, 1}``, that lies in the grid, in ascending column order, with 26.0
on the diagonal and -1.0 elsewhere.  The matrix holds
``(3nx-2)(3ny-2)(3nz-2)`` nonzeros.
"""
from __future__ import annotations

import itertools

import numpy as np
import scipy.sparse as sp

DIAGONAL = 26.0
OFF_DIAGONAL = -1.0


def matrix(config: dict) -> sp.csr_matrix:
    nx, ny, nz = (int(config[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    iz, iy, ix = (a.ravel() for a in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    row = np.arange(n, dtype=np.int64)
    cols, inside, vals = [], [], []
    # (sz, sy, sx) in lexicographic order: ascending column offsets
    for sz, sy, sx in itertools.product((-1, 0, 1), repeat=3):
        inside.append((iz + sz >= 0) & (iz + sz < nz) & (iy + sy >= 0) &
                      (iy + sy < ny) & (ix + sx >= 0) & (ix + sx < nx))
        cols.append(row + sz * nx * ny + sy * nx + sx)
        vals.append(DIAGONAL if sz == sy == sx == 0 else OFF_DIAGONAL)
    inside = np.stack(inside, axis=1)
    cols = np.stack(cols, axis=1)[inside].astype(np.int32)
    data = np.broadcast_to(np.asarray(vals, np.float32), inside.shape)[inside]
    indptr = np.concatenate([[0], np.cumsum(inside.sum(axis=1))])
    return sp.csr_matrix((data, cols, indptr), shape=(n, n))
