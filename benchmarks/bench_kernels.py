"""Kernel-level benchmark: Pallas BCSR kernels (interpret-validated) +
block-size roofline table for the TPU target.

Reports, per (block shape x N-tile): modeled T_e, arithmetic intensity,
whether the block is MXU-aligned, and the VMEM working set of the BlockSpec
tiling — the inputs to the §Perf kernel iteration.  Also cross-checks the
nnz-stream kernel against the oracle on a skewed matrix (the dc2 worst
case) and reports the waste factor a static (block-row x slot) schedule
would pay there (SMaT's documented weakness, fixed by nnz-streaming).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import bcsr as bcsr_lib
from repro.core import perf_model as pm
from repro.core import topology
from repro.kernels import bcsr_spmm as pk
from repro.kernels import ref

VMEM_BYTES = 128 * 2 ** 20     # ~128 MiB usable VMEM on v5e-class core


def run():
    rows = []
    # ---- block-size roofline table (TPU target)
    for h, w in [(8, 128), (16, 128), (32, 128), (128, 128), (256, 128),
                 (128, 256)]:
        for bn in (128, 256, 512):
            t_c, t_m, t_e = pm.block_mma_time(h, w, bn)
            ai = (2 * h * w * bn) / ((h * w + w * bn) * 2)
            vmem = (h * w + w * bn + h * bn * 2) * 4 * 2  # dbl-buffered f32
            aligned = (h % 16 == 0) and (w % 128 == 0) and (bn % 128 == 0)
            rows.append((
                f"kernel/block_{h}x{w}_bn{bn}", round(t_e * 1e9, 1),
                f"T_e_ns={t_e*1e9:.0f};bound={'mem' if t_m>t_c else 'mxu'};"
                f"AI={ai:.0f};vmem_kb={vmem/1024:.0f};"
                f"mxu_aligned={aligned};fits_vmem={vmem < VMEM_BYTES}"))

    # ---- dc2 worst case: static-schedule waste vs nnz-stream
    csr = topology.power_law(2048, 6.0, seed=3)
    a = bcsr_lib.from_scipy(csr, (16, 16)).ensure_nonempty_rows()
    bpr = a.blocks_per_row()
    waste = float(bpr.max() * a.n_block_rows) / max(float(bpr.sum()), 1)
    rng = np.random.default_rng(0)
    b = rng.standard_normal((a.n_block_cols * 16, 16)).astype(np.float32)

    got_stream = pk.bcsr_spmm_nnz_stream(
        jnp.asarray(a.vals), jnp.asarray(a.row_ids), jnp.asarray(a.col_ids),
        jnp.asarray(b), a.n_block_rows, bn=16, interpret=True)
    want = ref.bcsr_spmm_ref(jnp.asarray(a.vals), jnp.asarray(a.row_ids),
                             jnp.asarray(a.col_ids), jnp.asarray(b),
                             a.n_block_rows)
    ok_s = bool(np.allclose(np.asarray(got_stream), np.asarray(want),
                            atol=1e-4))
    rows.append(("kernel/dc2_static_schedule_waste", 0,
                 f"static_grid_steps/nnz_blocks={waste:.1f}x;"
                 f"stream_correct={ok_s};"
                 f"(nnz-stream pays 1.0x by construction)"))
    emit(rows)
    return rows


if __name__ == "__main__":
    run()
