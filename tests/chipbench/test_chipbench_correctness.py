"""``correct`` at a size a test run holds: a sound run passes, and the
control and each fault a cell can have fail, against the limits of the
configuration files.  The harness's look for a chip is skipped; the rest
of a run is driven with the Pallas interpreter on the CPU."""
import copy
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, harness

SEED = 2**31 + 99


def _cell(name, config_update, traffic):
    real = bench.load_cell(name)
    config = copy.deepcopy(real.config)
    config.update(config_update)
    return bench.Cell(name=real.name, chips=1, config_name=real.config_name,
                      config=config, traffic_name=real.traffic_name,
                      traffic=traffic, end_to_end=real.end_to_end,
                      per_layer=real.per_layer, repo=real.repo)


def lib_cell():
    return _cell("hpcg.spmm-n8", {"nx": 16, "ny": 16, "nz": 8},
                 {"driver": "spmm_loop", "op": "spmm", "n": 8, "panels": 2,
                  "backend": "auto", "sample": 2})


def run(cell, tmp_path, variant="program", seconds=0.5):
    opts = harness.Options(interpret=True, variant=variant,
                           cache_dir=tmp_path)
    result = harness.run_cell(cell, SEED, seconds, False,
                              time.perf_counter(), opts)
    assert result["attempted"] > 0
    return result


@pytest.mark.parametrize("variant", ["program", "control"])
def test_lib(variant, tmp_path):
    result = run(lib_cell(), tmp_path, variant)
    assert result["correct"] is (variant == "program")
    assert set(result["metrics"]) == {"lib_gflops", "setup_s"}
    assert list(result)[-1] == "checks"


def _altered(fwd):
    def wrapped(*args):
        out = fwd(*args)
        return out.at[0, 0].add(jnp.max(jnp.abs(out)))
    return wrapped


def _half_left_out(fwd):
    def wrapped(*args):
        out = fwd(*args)
        return out.at[:, out.shape[1] // 2:].set(0)
    return wrapped


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_lib_faults(fault, monkeypatch, tmp_path):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_fwd_impl", fault(ops._fwd_impl))
    assert run(lib_cell(), tmp_path)["correct"] is False


def _hpcg(nx, ny, nz):
    gen = bench.load_module(bench.PKG / "generators" / "hpcg_27pt.py")
    return gen.matrix({"nx": nx, "ny": ny, "nz": nz})


@pytest.mark.parametrize("grid", [(3, 3, 3), (16, 8, 4)])
def test_hpcg_matrix(grid):
    """HPCG's definition: (3nx-2)(3ny-2)(3nz-2) nonzeros, 26 on the
    diagonal, -1 for each neighbour, symmetric, sorted rows."""
    nx, ny, nz = grid
    a = _hpcg(nx, ny, nz)
    n = nx * ny * nz
    assert a.shape == (n, n)
    assert a.nnz == (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    assert np.all(a.diagonal() == 26.0)
    assert set(np.unique(a.data)) == {26.0, -1.0}
    assert (a != a.T).nnz == 0 and a.has_sorted_indices
    # an interior point has all 26 neighbours, a corner 7
    counts = np.diff(a.indptr)
    assert counts.max() == 27 and counts.min() == 8
    # row (1, 1, 1) of the 3^3 grid is the centre: every column, row 13
    if grid == (3, 3, 3):
        assert list(a[13].indices) == list(range(27))


def test_reference_against_dense():
    """The float32 segment-sum reference is the dense product, and the
    control's fp8 reading lies far above the bf16 rounding of the output."""
    from chipbench.reference import spmm as ref_spmm
    a = _hpcg(8, 4, 4)
    rng = np.random.default_rng(0)
    b = rng.standard_normal((a.shape[1], 16)).astype(np.float32)
    ref = ref_spmm.SpmmReference(a.indptr, a.indices, a.data, a.shape,
                                 jnp.float32, n=16, elements=16 * 18 * 6)
    assert ref.block_rows == 6 and a.shape[0] % 6   # a ragged last block
    dense = a.toarray() @ b
    got = np.concatenate([np.asarray(c) for _, _, c in ref.blocks(b)])
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-4)
    out = jnp.asarray(dense).astype(jnp.bfloat16)
    program = ref_spmm.rel_err(ref, b, out)
    control = ref_spmm.control_rel_err(ref, b, jnp.bfloat16)
    assert 0 < program < 0.005 < 0.02 < control
