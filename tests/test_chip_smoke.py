"""``chip_smoke.py``'s phases on the CPU at a tiny size, kernels in
interpret mode — the rehearsal that keeps the chip script's code paths
(reference comparisons, serve path, sharded path) working between chip
runs.  The script itself refuses to run without a TPU; these tests call
its phase functions directly."""
import dataclasses

import jax
import pytest

import chip_smoke as cs
from repro.configs import get_config

TINY = cs.Sizes(mip1_n=1024, mip1_nnz=20_000, block=(16, 128), ns=(8, 128),
                ref_rows=256, ref_entries=64, attn_len=256, attn_band=64,
                attn_heads=2, attn_head_dim=32, attn_block=(32, 32),
                attn_ref_rows=64, slots=2, cache_len=64, requests=3,
                prompt_len=5, new_tokens=4, sharded_n=128)


def _pallas_smoke_cfg():
    """The smoke view pins ``xla``; the chip path runs Pallas kernels."""
    cfg = get_config("smat-attn-1.3b:smoke")
    return dataclasses.replace(
        cfg,
        ffn_sparsity=dataclasses.replace(cfg.ffn_sparsity, backend="pallas"),
        attn_sparsity=dataclasses.replace(cfg.attn_sparsity,
                                          backend="fused"))


@pytest.mark.parametrize("phase", ["library", "server", "sharded"])
def test_chip_smoke_phase_on_cpu(phase, capsys):
    ck = cs.Checks()
    if phase == "library":
        ck.run(phase, cs.library_phase, TINY, True)
    elif phase == "server":
        ck.run(phase, cs.server_phase, TINY, _pallas_smoke_cfg())
    else:
        ck.run(phase, cs.sharded_phase, TINY, jax.device_count(), True)
    out = capsys.readouterr().out
    assert ck.failed == [], out
    assert f"[{phase}] ok" in out


def test_chip_smoke_refuses_cpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert cs.main([]) == 2
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err and '"ok"' not in captured.out
