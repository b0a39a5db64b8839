"""The readers of the program's own measurements: ``op_name`` from a
recorded chip trace (``chipbench/xplane_ops.py``), the device time under
the op's ``smat.*`` scopes, and the registry's stage and compile gauges.
CPU only: reading a trace touches no device."""
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench, harness, tracing, xplane_ops
from repro.obs import metrics

DATA = Path(__file__).parent / "data"
UNSCOPED = DATA / "tpu_v5e_spmm.xplane.pb"
# A traced window of six calls of the scoped op on one TPU v5e, as the
# harness records one: HPCG at 16^3 (4,096 rows), block (16, 128), the
# Jaccard reorder, N = 8, backend "pallas".  Per call, in ns of device
# time: %pad.0 (smat.pad) 346-348; the kernel %smat_spmm_nnz_stream.1
# about 190,500; under smat.epilogue %fusion 6,921-6,926, %fusion.1
# 348-451, %compare_and_fusion 11, %broadcast_select_fusion 2,356-2,358
# and %copy.1 858-861; %copy (B itself, op_name "b") 1,043-1,056 and a
# few ns of async copies carry no scope.
SCOPED = DATA / "tpu_v5e_spmm_scoped.xplane.pb"


def _reader(name):
    return bench.load_module(bench.PKG / "metrics" / f"{name}.py")


def test_op_names_from_the_recorded_trace():
    names = xplane_ops.op_names(UNSCOPED)
    by_instr = {}
    for text, op in names.items():
        by_instr.setdefault(text.split(" = ", 1)[0], set()).add(op)
    assert by_instr["%_lambda_.1"] == {"jit(<lambda>)/pallas_call"}
    assert by_instr["%fusion"] == {"jit(<lambda>)/gather"}
    assert by_instr["%closed_call.8"] == {
        "jit(scanned)/while/body/closed_call/pallas_call"}
    # every key is the HLO text an event of the trace is named by
    ops = {text for _, _, text in tracing.load(UNSCOPED).ops()}
    assert set(names) <= ops


def test_under():
    assert xplane_ops.under("jit(f)/smat.pad/jit(_pad)/pad", "smat.pad")
    assert xplane_ops.under("jit(f)/smat.epilogue/jit(_take)/gather",
                            "smat.epilogue")
    assert not xplane_ops.under("jit(f)/smat.padded/pad", "smat.pad")
    assert not xplane_ops.under("jit(f)/gather", "smat.epilogue")


def test_scope_readers_find_nothing_in_an_unscoped_trace(tmp_path,
                                                         monkeypatch):
    ctx = _traced_ctx(UNSCOPED, tmp_path, monkeypatch, calls=9)
    assert _reader("pad_ms.lib").read(ctx) is None
    assert _reader("epilogue_ms.lib").read(ctx) is None
    ctx.trace = None
    assert _reader("epilogue_ms.lib").read(ctx) is None


def test_scope_readers_on_the_scoped_trace(tmp_path, monkeypatch):
    ctx = _traced_ctx(SCOPED, tmp_path, monkeypatch, calls=6)
    assert sum(1 for *_, n in ctx.trace.spans if n == "bench.call") == 6
    # smat.pad: 346 + 346 + 348 + 347 + 346 + 347 = 2,080 ns
    assert _reader("pad_ms.lib").read(ctx) == pytest.approx(2080e-6 / 6)
    # smat.epilogue: 41,539 (%fusion) + 2,299 (%fusion.1) + 66
    # (%compare_and_fusion) + 14,143 (%broadcast_select_fusion) + 5,160
    # (%copy.1) = 63,207 ns
    assert _reader("epilogue_ms.lib").read(ctx) == pytest.approx(
        63207e-6 / 6)


def test_scoped_trace_names_the_kernel_and_its_pick():
    names = xplane_ops.op_names(SCOPED)
    kernel, = [op for text, op in names.items()
               if text.startswith("%smat_spmm_nnz_stream.1 ")]
    assert kernel == ("jit(<lambda>)/smat.kernel.pallas/"
                      "smat_spmm_nnz_stream/pallas_call")
    # every operation but B's copy and the async copies carries a scope
    tr = tracing.load(SCOPED)
    unscoped = [e - s for s, e, text in tr.ops()
                if "smat." not in names.get(text, "")]
    assert sum(unscoped) < 0.01 * tr.busy_s() * 1e9


def _traced_ctx(trace_path, tmp_path, monkeypatch, calls):
    """A reader's context over the trace at ``trace_path``, laid where the
    harness writes a cell's trace."""
    cell = "hpcg.spmm-n8"
    dest = tmp_path / harness.TRACE_DIR / cell / "plugins/profile/run"
    dest.mkdir(parents=True)
    shutil.copy(trace_path, dest / "host.xplane.pb")
    monkeypatch.setattr(bench, "CACHE", tmp_path)
    return SimpleNamespace(cell=SimpleNamespace(name=cell),
                           trace=tracing.load(trace_path), peak=None,
                           setup={}, window={"calls": calls})


STAGES = {"prepare_blocking_s": "blocking", "prepare_reorder_s": "reorder",
          "prepare_meta_s": "meta", "prepare_transfer_s": "to_device"}


def test_registry_readers_on_a_stub_snapshot(monkeypatch):
    gauges = {f"prepare.seconds{{stage={s}}}": 1.0 + i
              for i, s in enumerate(STAGES.values())}
    gauges.update({"jax.compile.seconds{phase=trace}": 0.25,
                   "jax.compile.seconds{phase=lower}": 0.5,
                   "jax.compile.seconds{phase=compile}": 2.0,
                   "jax.compile.seconds{phase=cache_load}": 0.125,
                   "serve.other": 99.0})
    snap = {"counters": {"jax.compiles": 3}, "gauges": gauges,
            "histograms": {}}
    monkeypatch.setattr(metrics, "snapshot", lambda: snap)
    ctx = SimpleNamespace(setup={}, window={}, trace=None)
    for i, name in enumerate(STAGES):
        assert _reader(name).read(ctx) == 1.0 + i
    assert _reader("compile_s").read(ctx) == 2.875

    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    monkeypatch.setattr(metrics, "snapshot", lambda: empty)
    for name in [*STAGES, "compile_s"]:
        assert _reader(name).read(ctx) is None
