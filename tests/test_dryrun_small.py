"""Dry-run machinery tests on a small virtual-device mesh (subprocess so the
XLA device-count flag applies cleanly), plus roofline HLO-parsing units."""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import roofline as rl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dryrun(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["DRYRUN_XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=500)


@pytest.mark.slow
def test_dryrun_all_shapes_small_mesh(tmp_path):
    out = tmp_path / "r.json"
    p = _run_dryrun("--arch", "h2o-danube-1.8b:smoke",
                    "--mesh-shape", "2,4", "--batch", "8", "--seq", "128",
                    "--no-extrapolate", "--out", str(out))
    assert p.returncode == 0, p.stdout + p.stderr
    records = json.loads(out.read_text())
    assert len(records) == 4
    assert all(r["status"] == "ok" for r in records)
    train = next(r for r in records if r["shape"] == "train_4k")
    assert train["roofline"]["flops_per_device"] > 0
    assert train["memory"]["peak_bytes_per_device"] > 0


@pytest.mark.slow
def test_dryrun_multipod_axes_small(tmp_path):
    out = tmp_path / "r.json"
    p = _run_dryrun("--arch", "mamba2-1.3b:smoke", "--shape", "train_4k",
                    "--mesh-shape", "2,2,2", "--batch", "8", "--seq", "64",
                    "--no-extrapolate", "--out", str(out))
    assert p.returncode == 0, p.stdout + p.stderr
    records = json.loads(out.read_text())
    assert records[0]["status"] == "ok"
    assert records[0]["mesh"] == "2x2x2"


# ---------------------------------------------------------- roofline parsing
HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[512,256]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = bf16[1024]{0} all-reduce(%x), replica_groups=[8,4]<=[32], to_apply=%add
  %rs = f32[64,64]{1,0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %cp = f32[32]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %d = f32[128,128]{1,0} dot(%a, %b)
}
"""


def test_parse_collectives_counts_and_bytes():
    st = rl.parse_collectives(HLO_SAMPLE)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "reduce-scatter": 1, "collective-permute": 1}
    assert st.bytes_total["all-gather"] == 512 * 256 * 4
    assert st.bytes_total["all-reduce"] == 1024 * 2
    # ring factors: AG (n-1)/n, AR 2(n-1)/n
    expected = (3 / 4) * 512 * 256 * 4 + 2 * (3 / 4) * 1024 * 2 + \
        (1 / 2) * 64 * 64 * 4 + 32 * 4
    assert abs(st.wire_bytes - expected) < 1e-6


def test_parse_collectives_ignores_done_ops():
    txt = """
  %ags = f32[256]{0} all-gather-start(%p), replica_groups={{0,1}}
  %agd = f32[256]{0} all-gather-done(%ags)
"""
    st = rl.parse_collectives(txt)
    assert st.counts.get("all-gather", 0) == 1


def test_roofline_terms_and_bottleneck():
    r = rl.compute_roofline(
        flops=197e12 * 0.010,        # 10 ms of compute
        bytes_acc=819e9 * 0.002,     # 2 ms of HBM
        wire_bytes=50e9 * 0.050,     # 50 ms of ICI
        n_devices=256, model_flops=197e12 * 0.010 * 256 * 0.5)
    assert r.bottleneck == "collective"
    assert abs(r.t_compute - 0.010) < 1e-9
    assert abs(r.useful_ratio - 0.5) < 1e-9


def test_model_flops_moe_uses_active_params():
    from repro.configs import SHAPES, get_config
    lite = get_config("deepseek-v2-lite-16b")
    total, active = lite.param_count(), lite.active_param_count()
    assert active < total * 0.45        # MoE: activates well under half
    mf = rl.model_flops_for(lite, SHAPES["train_4k"])
    assert mf == pytest.approx(6.0 * active * 4096 * 256)


def test_import_leaves_env_and_lowers_sparse_as_xla(monkeypatch):
    """Importing the dry-run (``launch.train`` does, for its shard report)
    must not touch ``XLA_FLAGS``; its lowering swaps the registered
    ``auto`` sparse backends for ``xla`` — no Pallas kernel compiles for
    the CPU."""
    import importlib

    from repro.configs import get_config
    from repro.launch import dryrun
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    importlib.reload(dryrun)
    assert "XLA_FLAGS" not in os.environ
    cfg = get_config("smat-attn-1.3b")
    assert cfg.ffn_sparsity.backend == cfg.attn_sparsity.backend == "auto"
    low = dryrun.xla_lowered(cfg)
    assert low.ffn_sparsity.backend == low.attn_sparsity.backend == "xla"
    assert dryrun.xla_lowered(get_config("h2o-danube-1.8b")) is \
        get_config("h2o-danube-1.8b")
