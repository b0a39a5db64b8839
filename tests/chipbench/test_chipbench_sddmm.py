"""The ``hpcg.sddmm-n512`` cell (driver ``sddmm_loop``) at a size a test
run holds: its counts and roofline reader on hand-worked numbers, the
reference against a dense product, and ``correct`` (a sound run passes;
the fp8 control and two faults fail, against the limit its traffic
gives).  The harness's look for a chip is skipped; the rest of a run is
driven on the CPU."""
import copy
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, counts_models, harness, peaks

SEED = 2**31 + 99
V5E = peaks.peaks("TPU v5 lite")
CELL = "hpcg.sddmm-n512"


def test_sddmm_counts_hpcg_n512():
    # 6,859,000 nonzeros of HPCG's 64^3 stencil, X and Y 262,144 x 512 bf16
    ops, nbytes = counts_models.sddmm_counts(
        nnz=6_859_000, n_rows=262_144, n_cols=262_144, n=512, io_bytes=2,
        out_bytes=2)
    assert ops == 7_023_616_000
    # X and Y 268,435,456 each + values 13,718,000 + indices 27,436,000
    assert nbytes == 578_024_912
    reader = bench.load_module(bench.PKG / "metrics" /
                               "kernel_roofline.sddmm.py")
    setup = {"shape": (262_144, 262_144), "nnz": 6_859_000, "n": 512,
             "io_bytes": 2, "out_bytes": 2}

    class Trace:
        def ops(self, pattern):
            assert pattern == 'custom_call_target="tpu_custom_call"'
            return [(0.0, 1e7, "k")] * 3                 # 30 ms in all
    ctx = SimpleNamespace(trace=Trace(), peak=V5E, setup=setup,
                          window={"calls": 2})
    assert reader.read(ctx) == pytest.approx(
        100 * (578_024_912 / 819e9) * 2 / 0.03)         # 0.706 ms a call
    ctx.trace = None
    assert reader.read(ctx) is None


def _cell():
    real = bench.load_cell(CELL)
    assert real.traffic["n"] == 512 and real.traffic["pairs"] == 4
    config = copy.deepcopy(real.config)
    config.update(nx=16, ny=16, nz=8)
    traffic = dict(real.traffic, n=128, pairs=2, sample=2)
    return bench.Cell(name=real.name, chips=1, config_name=real.config_name,
                      config=config, traffic_name=real.traffic_name,
                      traffic=traffic, end_to_end=real.end_to_end,
                      per_layer=real.per_layer, repo=real.repo)


def run(tmp_path, variant="program"):
    opts = harness.Options(interpret=True, variant=variant,
                           cache_dir=tmp_path)
    result = harness.run_cell(_cell(), SEED, 0.5, False, time.perf_counter(),
                              opts)
    assert result["attempted"] > 0
    return result


@pytest.mark.parametrize("variant", ["program", "control"])
def test_sddmm_cell(variant, tmp_path):
    result = run(tmp_path, variant)
    assert result["correct"] is (variant == "program")
    assert set(result["metrics"]) == {"lib_gflops", "setup_s"}


def _altered(impl):
    def wrapped(*args):
        out = impl(*args)
        return out.at[0].add(jnp.max(jnp.abs(out)))
    return wrapped


def _half_left_out(impl):
    def wrapped(cfg, meta, arrays, x, y):
        n = x.shape[1]
        return impl(cfg, meta, arrays, x.at[:, n // 2:].set(0), y)
    return wrapped


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_sddmm_faults(fault, monkeypatch, tmp_path):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_sddmm_impl", fault(ops._sddmm_impl))
    assert run(tmp_path)["correct"] is False


def test_reference_and_positions_against_dense():
    """The reference's values are the dense ``X @ Y^T`` at the nonzeros
    in CSR order, and the driver finds each nonzero in the program's
    reordered blocks."""
    from chipbench.reference import sddmm as ref_sddmm
    from repro.core import bcsr as bcsr_lib
    from repro.kernels import ops
    gen = bench.load_module(bench.PKG / "generators" / "hpcg_27pt.py")
    a = gen.matrix({"nx": 8, "ny": 4, "nz": 4})
    rng = np.random.default_rng(0)
    x = rng.standard_normal((a.shape[0], 16)).astype(np.float32)
    y = rng.standard_normal((a.shape[1], 16)).astype(np.float32)
    ref = ref_sddmm.SddmmReference(a.indptr, a.indices, a.shape, 16,
                                   elements=16 * 128)
    assert ref.length == 128 and a.nnz % 128           # a ragged last block
    got = np.concatenate([np.asarray(v) for _, _, v in ref.blocks(x, y)])
    coo = a.tocoo()
    dense = (x @ y.T)[coo.row, coo.col]
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)

    arrays, meta = ops.prepare_sparse(bcsr_lib.from_scipy(a, (16, 128)),
                                      jnp.float32, reorder="jaccard")
    drv = bench.load_module(bench.PKG / "drivers" / "sddmm_loop.py")
    pos = drv.nonzero_positions(a, arrays, meta)
    assert (pos >= 0).all()
    vals = np.asarray(ops.sddmm(arrays, meta, jnp.asarray(x),
                                jnp.asarray(y), backend="xla"))
    np.testing.assert_allclose(vals.reshape(-1)[pos], dense, rtol=1e-5,
                               atol=1e-5)
    program = ref_sddmm.rel_err(ref, x, y, jnp.asarray(
        vals.reshape(-1)[pos]).astype(jnp.bfloat16))
    control = ref_sddmm.control_rel_err(ref, x, y, jnp.bfloat16)
    assert 0 < program < 0.005 < 0.02 < control
