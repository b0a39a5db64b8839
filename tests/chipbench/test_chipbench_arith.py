"""The yardstick's arithmetic on hand-worked numbers: operations and
bytes, least times, the end-to-end metrics and every per-layer reader."""
from types import SimpleNamespace

import pytest

from chipbench import bench, counts, peaks

V5E = peaks.peaks("TPU v5 lite")


def test_peaks_keyed_by_device_kind():
    assert V5E["bf16_flops_s"] == 197e12 and V5E["hbm_bytes_s"] == 819e9
    assert "TPU v5e" in V5E["source"]
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.peaks("TPU v9 imaginary")


def test_spmm_counts_hpcg_n8():
    # 6,859,000 nonzeros of HPCG's 64^3 stencil times an 8-column bf16 panel
    ops, nbytes = counts.spmm_counts(
        nnz=6_859_000, n_rows=262_144, n_cols=262_144, n=8, val_bytes=2,
        io_bytes=2, index_bytes=4 * 6_859_000)
    assert ops == 109_744_000
    # values 13,718,000 + indices 27,436,000 + B and C 4,194,304 each
    assert nbytes == 49_542_608
    least = counts.least_time_s(ops, nbytes, V5E)
    assert least == pytest.approx(49_542_608 / 819e9)      # 60.5 us
    # a compute-bound problem takes its operations over the FLOP/s
    assert counts.least_time_s(2e12, 1.0, V5E) == pytest.approx(2e12 / 197e12)


def _driver(name):
    return bench.load_module(bench.PKG / "drivers" / f"{name}.py")


def test_lib_gflops():
    st = SimpleNamespace(nnz=1000, n=8)
    e2e = _driver("spmm_loop").end_to_end(st, {"calls": 100, "seconds": 2.0})
    assert e2e == {"lib_gflops": pytest.approx(8e-4)}


class FakeTrace:
    def __init__(self, window_s, busy_s, kernels=()):
        self.window_s, self._busy = window_s, busy_s
        self._kernels = list(kernels)

    def busy_s(self):
        return self._busy

    def ops(self, pattern=None):
        assert pattern == 'custom_call_target="tpu_custom_call"'
        return self._kernels


def _read(name, **ctx):
    reader = bench.load_module(bench.PKG / "metrics" / f"{name}.py")
    ctx.setdefault("peak", V5E)
    return reader.read(SimpleNamespace(**ctx))


def test_lib_readers():
    setup = {"shape": (262_144, 262_144), "nnz": 6_859_000, "n": 8,
             "val_bytes": 2, "io_bytes": 2, "prepare_s": 13.5,
             "block_fill": 0.035}
    kernels = [(0.0, 5e6, "k")] * 4                       # 20 ms in all
    share = _read("kernel_roofline.lib", setup=setup, window={"calls": 10},
                  trace=FakeTrace(1.0, 0.5, kernels))
    assert share == pytest.approx(100 * (49_542_608 / 819e9) * 10 / 0.02)
    assert _read("kernel_roofline.lib", setup=setup, window={"calls": 10},
                 trace=FakeTrace(1.0, 0.5, [])) is None
    assert _read("device_idle.lib", trace=FakeTrace(2.0, 0.5)) == 75.0
    assert _read("prepare_s", setup=setup) == 13.5
    assert _read("block_fill", setup=setup) == pytest.approx(3.5)


def test_every_reader_is_a_file_of_its_own():
    names = {m["name"] for m in bench.load_benchmark()["per_layer"]}
    files = {p.name[:-3] for p in (bench.PKG / "metrics").glob("*.py")}
    assert names <= files
