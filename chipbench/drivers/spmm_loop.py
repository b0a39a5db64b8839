"""Library cells: one caller in a closed loop around one library op.

Traffic parameters (``chipbench/traffic/<mix>.json``):

* ``op``: ``"spmm"``, the op the window drives (``ops.spmm``);
* ``n``: columns of each dense panel B;
* ``panels``: how many seeded panels the calls take in turn;
* ``backend``: what the caller passes to the op (``"auto"``);
* ``sample``: how many of the window's products the check compares.

Each call takes the next panel and is waited for before the next call, as
in an iterative solver that needs each product.  The matrix comes from the
configuration's generator (``chipbench/generators/``) and is handed to the
program as a user would (``from_scipy`` then ``prepare_sparse`` with the
configured block shape and reorder); the panels come from the seed.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, seeds
from chipbench.harness import Check
from chipbench.reference import spmm as ref_spmm


class State:
    pass


def _panels(key, count: int, k: int, n: int, dtype) -> list:
    """``count`` panels of ``(k, n)``, drawn on the device in one call in
    the operand dtype (a float32 copy would set the run's memory peak),
    each an array of its own (slices of one stack would be copies)."""
    keys = jax.random.split(key, count)
    return jax.jit(lambda keys: [jax.random.normal(kk, (k, n), dtype)
                                 for kk in keys])(keys)


def setup(cell, seed: int, opts) -> State:
    from repro.core import bcsr as bcsr_lib
    from repro.kernels import ops

    cfg, traffic = cell.config, cell.traffic
    if traffic["op"] != "spmm":
        raise ValueError(f"spmm_loop drives op 'spmm', not {traffic['op']!r}")
    st = State()
    st.dtype = jnp.dtype(cfg["dtype"])
    st.limits = cfg["checks"]
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
    h, w = meta.block
    st.record = {"prepare_s": prepare_s, "nnz": st.nnz, "shape": (m, k),
                 "n": st.n, "nnzb": meta.nnzb,
                 "block_fill": st.nnz / (meta.nnzb * h * w),
                 "val_bytes": st.dtype.itemsize, "io_bytes": st.dtype.itemsize}
    st.panels = _panels(seeds.jax_key(seed, "panels"), int(traffic["panels"]),
                        k, st.n, st.dtype)
    backend, interpret = traffic["backend"], opts.interpret
    st.arrays = arrays
    st.fn = jax.jit(lambda ar, b: ops.spmm(ar, meta, b, backend=backend,
                                           interpret=interpret))
    for b in st.panels[:2]:                    # compile, then warm
        jax.block_until_ready(st.fn(st.arrays, b))
    st.rng = np.random.default_rng(seeds.numpy_seed(seed, "sample"))
    st.sample_size = int(traffic["sample"])
    return st


def window(st: State, seconds: float) -> dict:
    """Calls until ``seconds`` have passed; a reservoir sample, drawn from
    the seed, of the products keeps ``sample`` of them for the check."""
    panels = st.panels
    sample = []
    calls = 0
    t_open = time.perf_counter()
    t_end = t_open
    while t_end - t_open < seconds:
        p = calls % len(panels)
        with jax.profiler.TraceAnnotation("bench.call"):
            out = st.fn(st.arrays, panels[p])
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
    """Drop the program's arrays and op; the products sampled stay."""
    del st.arrays, st.fn


def check(st: State, win: dict, opts) -> list:
    ref = ref_spmm.SpmmReference(st.csr.indptr, st.csr.indices, st.csr.data,
                                 st.csr.shape, st.dtype, st.n)
    worst = 0.0
    for _, p, out in win["sample"]:
        b = st.panels[p]
        if opts.variant == "control":
            err = ref_spmm.control_rel_err(ref, b, out.dtype)
        else:
            err = ref_spmm.rel_err(ref, b, out)
        worst = max(worst, err if math.isfinite(err) else math.inf)
    return [Check("spmm_rel_err", worst,
                  float(st.limits["spmm_rel_err"]))]


def end_to_end(st: State, win: dict) -> dict:
    ops = 2 * st.nnz * st.n * win["calls"]
    return {"lib_gflops": ops / win["seconds"] / 1e9}
