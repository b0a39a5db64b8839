"""The ``deepseek-v2-lite`` cell at a size a test run holds: the useful
operations on hand-worked numbers, its per-layer readers, and ``correct``
(a sound run passes; the fp8 control, two faults of the routed experts
and one of the weights' conversion fail, against the limits of the
configuration file).  The harness's look for a chip is
skipped; the rest of a run is driven on the CPU."""
import copy
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from chipbench import bench, counts_models, harness, op_scopes, peaks

SEED = 2**31 + 99
CELL = "deepseek-v2-lite.prefill-8x4k"
V5E = peaks.peaks("TPU v5 lite")
# a CPU size: the configuration's 64 experts, top-6 and two shared, a
# leading dense layer and two MoE layers, narrower and shorter
SMALL = dict(num_hidden_layers=3, hidden_size=256, intermediate_size=512,
             vocab_size=1024, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=64, qk_rope_head_dim=16, qk_nope_head_dim=32,
             v_head_dim=32, moe_intermediate_size=128)


def _config():
    return bench.load_cell(CELL).config


def test_config_file_is_the_published_model_cut_in_depth():
    c = _config()
    entry = next(x for x in bench.load_benchmark()["configs"]
                 if x["name"] == "deepseek-v2-lite")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 7 and c["published"] == {
        "num_hidden_layers": 27}
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"],
            c["kv_lora_rank"], c["first_k_dense_replace"]) == \
        (2048, 10944, 1408, 64, 6, 2, 512, 1)
    drv = bench.load_module(bench.PKG / "drivers" / "prefill_loop.py")
    cfg = drv.model_config(c, None)
    assert (cfg.n_layers, cfg.d_ff, cfg.expert_d_ff, cfg.moe_dispatch) == \
        (7, 10944, 1408, "bcsr")
    assert not cfg.norm_topk_prob and cfg.rope_scaling.factor == 40
    # 4.0B parameters here: the dense layer, six MoE layers, both ends
    assert cfg.param_count() == pytest.approx(4.008e9, rel=1e-3)


def test_prefill_flops_hand_worked():
    """Per token: MLA projections 2 (2048*3072 + 2048*576 + 512*4096 +
    2048*2048) = 27,525,120 in each of 7 layers; the dense FFN 6 * 2048 *
    10944 = 134,479,872; per MoE layer the router 262,144, 2 shared and 6
    routed experts 8 * 6 * 2048 * 1408 = 138,412,032.  Per sequence of
    4,096: causal attention 2 * 16 * (4096 * 4097 / 2) * 320 per layer and
    the head 2 * 2048 * 102400."""
    c = _config()
    per_token = 7 * 27_525_120 + 134_479_872 + 6 * (262_144 + 138_412_032)
    per_seq = 7 * 2 * 16 * (4096 * 4097 // 2) * 320 + 2 * 2048 * 102_400
    flops = counts_models.prefill_flops(c, 8, 4096)
    assert flops == 8 * (4096 * per_token + per_seq)
    assert flops / 32768 == pytest.approx(1.306e9, rel=1e-3)
    moe = counts_models.moe_expert_counts(c, 32768)
    pairs = 32768 * 6
    assert moe["ops"] == 6 * pairs * 6 * 2048 * 1408
    assert moe["bytes"] == 6 * (3 * 64 * 2048 * 1408 * 2 +
                                2 * pairs * 2048 * 2)


def _read(name, **ctx):
    reader = bench.load_module(bench.PKG / "metrics" / f"{name}.py")
    ctx.setdefault("peak", V5E)
    ctx.setdefault("cell", SimpleNamespace(name=CELL))
    return reader.read(SimpleNamespace(**ctx))


def test_model_readers(monkeypatch):
    from repro.obs import metrics
    setup = {"flops_per_step": 4e13, "moe": {"ops": 2e13, "bytes": 1.6e10}}
    window = {"calls": 10, "seconds": 5.0}
    busy = SimpleNamespace(busy_s=lambda: 4.0)       # of a 5 s window
    assert _read("prefill_mfu", setup=setup, window=window, trace=busy) == \
        pytest.approx(100 * 4e14 / 4.0 / 197e12)
    assert _read("prefill_mfu", setup=setup, window=window, trace=busy,
                 peak=None) is None
    assert _read("prefill_mfu", setup=setup, window=window,
                 trace=None) is None
    kernel = 'x = f32[] custom-call(), custom_call_target="tpu_custom_call"'
    ops = [(2e8, kernel, "jit(s)/while/body/smat.moe.experts/"
            "smat.kernel.pallas/smat_sddmm_nnz_stream/pallas_call"),
           (1e8, "y = f32[] fusion()", "jit(s)/while/body/smat.moe.route/"
            "dot_general"),
           (3e8, "z = f32[] fusion()", "jit(s)/while/body/model.mla/dot"),
           (5e7, kernel, "jit(s)/while/body/model.mla/pallas_call")]
    monkeypatch.setattr(op_scopes, "scoped_ops", lambda ctx: ops)
    assert _read("moe_ms.prefill", window=window) == pytest.approx(30.0)
    assert _read("attn_ms.prefill", window=window) == pytest.approx(35.0)
    least = max(2e13 / 197e12, 1.6e10 / 819e9)         # compute-bound
    assert _read("kernel_roofline.moe", setup=setup, window=window) == \
        pytest.approx(100 * least * 10 / 0.2)
    monkeypatch.setattr(op_scopes, "scoped_ops", lambda ctx: ops[2:])
    assert _read("moe_ms.prefill", window=window) is None  # no such scope
    assert _read("kernel_roofline.moe", setup=setup, window=window) is None
    metrics.reset()
    assert _read("moe_pad_share") is None
    metrics.counter("moe.rows", kind="routed").inc(1000)
    metrics.counter("moe.rows", kind="padding").inc(25)
    assert _read("moe_pad_share") == pytest.approx(2.5)
    metrics.reset()


def _cell():
    real = bench.load_cell(CELL)
    config = copy.deepcopy(real.config)
    config.update(SMALL)
    traffic = {"driver": "prefill_loop", "batch": 2, "seq_len": 1024,
               "batches": 2, "zipf": 1.0, "sample": 2, "decode_steps": 3}
    return bench.Cell(name=real.name, chips=1, config_name=real.config_name,
                      config=config, traffic_name=real.traffic_name,
                      traffic=traffic, end_to_end=real.end_to_end,
                      per_layer=real.per_layer, repo=real.repo)


def run(tmp_path, variant="program"):
    opts = harness.Options(interpret=True, variant=variant,
                           cache_dir=tmp_path)
    result = harness.run_cell(_cell(), SEED, 0.3, False, time.perf_counter(),
                              opts)
    assert result["attempted"] > 0
    return result


@pytest.mark.parametrize("variant", ["program", "control"])
def test_model_cell(variant, tmp_path):
    result = run(tmp_path, variant)
    assert result["correct"] is (variant == "program")
    assert set(result["metrics"]) == {"lib_gflops", "setup_s"}
    assert set(result["checks"]) == {"logit_gap", "routed_rel_err"}
    limits = _config()["checks"]
    # both checks fail the fp8 control on their own
    failed = {k for k, c in result["checks"].items()
              if not c["value"] <= limits[k]}
    assert failed == (set() if variant == "program" else set(limits))


def _drop_past_first_block(expert_rows):
    """Pairs past their expert's first block lose their row: their
    tokens get nothing from that expert, as a capacity of one block
    would give."""
    def wrapped(gate_idx, n_experts, block_rows):
        rows = expert_rows(gate_idx, n_experts, block_rows)
        first = rows["expert_of_block"][rows["row_of_pair"] // block_rows]
        start = jnp.searchsorted(rows["expert_of_block"], first)
        late = rows["row_of_pair"] - start * block_rows >= block_rows
        out = rows["row_of_pair"].shape[0] * 1000          # past every row
        rows["row_of_pair"] = jnp.where(late, out, rows["row_of_pair"])
        return rows
    return wrapped


def _last_layer_left_out(moe_bcsr, n_moe):
    """The routed output of every ``n_moe``-th MoE layer traced (the last
    of each forward, the stacks unrolled) left out."""
    calls = [0]

    def wrapped(cfg, p, x, **kw):
        y, aux, stats = moe_bcsr(cfg, p, x, **kw)
        calls[0] += 1
        if calls[0] % n_moe == 0:
            y = jnp.zeros_like(y)
        return y, aux, stats
    return wrapped


def _routed_fails(result):
    """The run is not correct, and ``routed_rel_err`` alone says so."""
    assert result["correct"] is False
    limit = _config()["checks"]["routed_rel_err"]
    assert result["checks"]["routed_rel_err"]["value"] > 10 * limit


def test_model_fault_tokens_dropped(monkeypatch, tmp_path):
    from repro.models import moe as M
    monkeypatch.setattr(M, "expert_rows", _drop_past_first_block(
        M.expert_rows))
    _routed_fails(run(tmp_path))


def test_model_fault_last_layer_left_out(monkeypatch, tmp_path):
    from repro.models import moe as M
    from repro.models import unroll as U
    monkeypatch.setattr(M, "_moe_bcsr", _last_layer_left_out(
        M._moe_bcsr, SMALL["num_hidden_layers"] - 1))
    with U.unroll_scans():
        _routed_fails(run(tmp_path))


def test_model_fault_rope_rows_left_interleaved(monkeypatch, tmp_path):
    """The conversion leaves the rope rows in the published order, so the
    program rotates the wrong pairs: the reference, which reads the
    published layout itself, tells them apart."""
    drv = bench.load_module(bench.PKG / "drivers" / "prefill_loop.py")
    monkeypatch.setattr(drv, "_rope_rows", lambda w, start, rd: w)
    monkeypatch.setattr(bench, "driver", lambda cell: drv)
    result = run(tmp_path)
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > \
        _config()["checks"]["logit_gap"]
