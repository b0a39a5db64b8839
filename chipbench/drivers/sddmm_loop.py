"""SDDMM library cells: one caller in a closed loop around ``ops.sddmm``.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``op``: ``"sddmm"``, the op the window drives;
* ``n``: columns of each dense panel X and Y;
* ``pairs``: how many seeded ``(X, Y)`` pairs the calls take in turn;
* ``backend``: what the caller passes to the op (``"auto"``);
* ``sample``: how many of the window's outputs the check compares;
* ``checks``: the limit of ``sddmm_rel_err`` (with the traffic, since the
  configuration files hold the SpMM check's limits only).

Each call samples ``X @ Y^T`` at the matrix's structure and is waited for
before the next.  The matrix comes from the configuration's generator
and is prepared as the SpMM cells prepare it (``from_scipy`` then
``prepare_sparse`` with the configured block shape and reorder); X is
``[rows, n]`` and Y ``[cols, n]``, drawn from the seed.  The check reads
the program's blocks at the matrix's true nonzeros, in the CSR's order
(``sddmm_rel_err``, against ``chipbench/reference/sddmm.py``).
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, seeds
from chipbench.harness import Check
from chipbench.reference import sddmm as ref_sddmm


class State:
    pass


def _pairs(key, count: int, m: int, k: int, n: int, dtype) -> list:
    """``count`` pairs ``(X [m, n], Y [k, n])`` drawn on the device in one
    call in the operand dtype, each array its own buffer."""
    keys = jax.random.split(key, 2 * count)
    arrays = jax.jit(lambda keys: [
        jax.random.normal(kk, (m if i % 2 == 0 else k, n), dtype)
        for i, kk in enumerate(keys)])(keys)
    return list(zip(arrays[0::2], arrays[1::2]))


def nonzero_positions(csr, arrays, meta) -> np.ndarray:
    """``[nnz]`` int64: where each true nonzero of ``csr``, in its CSR
    order, lies in the flattened ``[nnzb, h, w]`` output of the prepared
    structure (its rows permuted by the structure's reorder); -1 where the
    structure holds no block for it."""
    h, w = meta.block
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    cols = np.asarray(csr.indices, np.int64)
    if arrays.inv_perm is not None and meta.reorder != "identity":
        rows = np.asarray(arrays.inv_perm, np.int64)[rows]
    nbc = meta.n_block_cols
    keys = (np.asarray(arrays.row_ids, np.int64) * nbc +
            np.asarray(arrays.col_ids, np.int64))
    want = (rows // h) * nbc + cols // w
    s = np.searchsorted(keys, want)
    found = (s < keys.size) & (keys[np.minimum(s, keys.size - 1)] == want)
    flat = s * h * w + (rows % h) * w + cols % w
    return np.where(found, flat, -1)


def setup(cell, seed: int, opts) -> State:
    from repro.core import bcsr as bcsr_lib
    from repro.kernels import ops

    cfg, traffic = cell.config, cell.traffic
    if traffic["op"] != "sddmm":
        raise ValueError(f"sddmm_loop drives op 'sddmm', not "
                         f"{traffic['op']!r}")
    st = State()
    st.dtype = jnp.dtype(cfg["dtype"])
    st.limits = traffic["checks"]
    st.n = int(traffic["n"])
    st.csr = bench.generator(cell).matrix(cfg)
    st.nnz = int(st.csr.nnz)
    m, k = st.csr.shape

    with jax.profiler.TraceAnnotation("bench.prepare"):
        t = time.perf_counter()
        a = bcsr_lib.from_scipy(st.csr, tuple(cfg["block"]))
        arrays, meta = ops.prepare_sparse(a, st.dtype,
                                          reorder=cfg["reorder"])
        jax.block_until_ready(arrays)
        prepare_s = time.perf_counter() - t
    del a
    st.positions = jnp.asarray(
        nonzero_positions(st.csr, arrays, meta).astype(np.int32))
    st.record = {"prepare_s": prepare_s, "nnz": st.nnz, "shape": (m, k),
                 "n": st.n, "nnzb": meta.nnzb,
                 "io_bytes": st.dtype.itemsize,
                 "out_bytes": st.dtype.itemsize}
    st.pairs = _pairs(seeds.jax_key(seed, "panels"), int(traffic["pairs"]),
                      m, k, st.n, st.dtype)
    backend, interpret = traffic["backend"], opts.interpret
    st.arrays = arrays
    st.fn = jax.jit(lambda ar, x, y: ops.sddmm(ar, meta, x, y,
                                               backend=backend,
                                               interpret=interpret))
    for x, y in st.pairs[:2]:                   # compile, then warm
        jax.block_until_ready(st.fn(st.arrays, x, y))
    st.rng = np.random.default_rng(seeds.numpy_seed(seed, "sample"))
    st.sample_size = int(traffic["sample"])
    return st


def window(st: State, seconds: float) -> dict:
    """Calls until ``seconds`` have passed; a reservoir sample, drawn from
    the seed, of the outputs keeps ``sample`` of them for the check."""
    pairs = st.pairs
    sample = []
    calls = 0
    t_open = time.perf_counter()
    t_end = t_open
    while t_end - t_open < seconds:
        p = calls % len(pairs)
        with jax.profiler.TraceAnnotation("bench.call"):
            out = st.fn(st.arrays, *pairs[p])
            out.block_until_ready()
        t_end = time.perf_counter()
        if len(sample) < st.sample_size:
            sample.append((calls, p, out))
        else:
            j = int(st.rng.integers(0, calls + 1))
            if j < st.sample_size:
                sample[j] = (calls, p, out)
        calls += 1
    return {"calls": calls, "seconds": t_end - t_open, "sample": sample,
            "attempted": calls, "failed": 0}


def free(st: State) -> None:
    """Drop the program's arrays and op; the outputs sampled stay."""
    del st.arrays, st.fn


@jax.jit
def _at_nonzeros(out, positions):
    flat = out.reshape(-1)
    return jnp.where(positions >= 0, flat[jnp.maximum(positions, 0)]
                     .astype(jnp.float32), jnp.nan)


def check(st: State, win: dict, opts) -> list:
    ref = ref_sddmm.SddmmReference(st.csr.indptr, st.csr.indices,
                                   st.csr.shape, st.n)
    worst = 0.0
    for _, p, out in win["sample"]:
        x, y = st.pairs[p]
        if opts.variant == "control":
            err = ref_sddmm.control_rel_err(ref, x, y, out.dtype)
        else:
            err = ref_sddmm.rel_err(ref, x, y,
                                    _at_nonzeros(out, st.positions))
        worst = max(worst, err if math.isfinite(err) else math.inf)
    return [Check("sddmm_rel_err", worst,
                  float(st.limits["sddmm_rel_err"]))]


def end_to_end(st: State, win: dict) -> dict:
    ops = 2 * st.nnz * st.n * win["calls"]
    return {"lib_gflops": ops / win["seconds"] / 1e9}
