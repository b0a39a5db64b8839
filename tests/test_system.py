"""End-to-end behaviour tests: the paper's pipeline as a system —
CSR -> reorder -> BCSR -> kernels inside a model -> train -> checkpoint ->
serve — wired together exactly as the launchers do."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.core import bcsr as bcsr_lib
from repro.core import reorder, topology
from repro.kernels import ops
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.optim import adamw
from repro.serve.engine import Request, ServeEngine
from repro.train.loop import train


def test_paper_pipeline_end_to_end():
    """The full SMaT pipeline on one matrix: reorder reduces blocks, kernels
    agree with dense, gradients flow through the sparse op."""
    csr = topology.blocked_random(n=512, nnz_target=8_000, cluster=32,
                                  seed=0)
    perm = reorder.jaccard_rows(csr, block_w=16, tau=0.7)
    a0 = bcsr_lib.from_scipy(csr, (16, 16))
    a1 = bcsr_lib.from_scipy(reorder.apply_perm(csr, perm), (16, 16))
    assert a1.nnzb < a0.nnzb                     # preprocessing worked

    arrays, meta = ops.prepare_sparse(a1.ensure_nonempty_rows(),
                                      dtype=jnp.float32)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(
        (meta.n_block_cols * 16, 24)).astype(np.float32))
    y_k = ops.spmm(arrays, meta, b, backend="pallas", interpret=True)
    y_d = ops.spmm(arrays, meta, b, backend="dense")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_d),
                               rtol=1e-3, atol=1e-3)

    g = jax.grad(lambda v: jnp.sum(
        ops.spmm(arrays._replace(vals=v), meta, b, backend="xla") ** 2))(
            arrays.vals)
    assert float(jnp.abs(g).sum()) > 0


def test_sparse_lm_train_then_serve(tmp_path):
    """Train the paper-technique LM a few steps, checkpoint, reload into a
    serving engine, decode — the whole deployment loop."""
    cfg = dataclasses.replace(get_config("smat-ffn-1.3b:smoke"),
                              dtype="float32")
    shape = ShapeCell("sys", "train", 32, 2)
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    res = train(cfg, shape, mesh, total_steps=6,
                opt_cfg=adamw.AdamWConfig(lr=1e-3, total_steps=6,
                                          warmup_steps=1),
                ckpt_dir=str(tmp_path), ckpt_every=3)
    assert all(np.isfinite(res.losses))

    # reload the final checkpoint and serve from it
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    like = {"params": T.param_specs(cfg),
            "opt": jax.eval_shape(adamw.init, T.param_specs(cfg))}
    state, step = mgr.restore(like)
    assert step == 6

    eng = ServeEngine(cfg, state["params"], n_slots=1, cache_len=16)
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=3))
    done = eng.run()
    assert len(done[0].out_tokens) == 3


def test_doctest_module_list_is_live():
    """``tests/doctest_modules.txt`` is the single source of truth for
    which modules CI runs ``--doctest-modules`` over.  Guard it against
    import rot: every listed file must exist AND import cleanly (a renamed
    or deleted module would otherwise fail only in the workflow, not
    locally), and the PR-6 fused-attention kernel must stay on the list so
    its docstring example keeps executing as a test."""
    import importlib
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    listing = os.path.join(root, "tests", "doctest_modules.txt")
    paths = [ln.strip() for ln in open(listing) if ln.strip()]
    assert paths, "doctest_modules.txt is empty"
    assert "src/repro/kernels/bcsr_attn.py" in paths
    for rel in paths:
        assert os.path.exists(os.path.join(root, rel)), \
            f"doctest_modules.txt lists missing file {rel}"
        assert rel.startswith("src/") and rel.endswith(".py"), rel
        mod_name = rel[len("src/"):-len(".py")].replace("/", ".")
        importlib.import_module(mod_name)


def test_benchmark_modules_importable():
    """Every module benchmarks/run.py can dispatch to — the gated SUITE
    and the report-only FIGURES — must stay importable, with the expected
    entry points.  CI runs only the gated suite; this keeps the figure
    modules from silently bit-rotting (they used to be orphans)."""
    import importlib
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    run = importlib.import_module("benchmarks.run")
    for mod_name, baseline in run.SUITE:
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        assert callable(mod.run) and callable(mod.diff), mod_name
        assert os.path.exists(os.path.join(root, "benchmarks", baseline)), \
            f"{mod_name}: committed baseline {baseline} missing"
    for mod_name in run.FIGURES:
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        assert callable(mod.run), mod_name
    assert callable(
        importlib.import_module("benchmarks.compare_sweeps").main)


def test_compile_cache_lands_in_env_dir(tmp_path, monkeypatch):
    """``enable_compile_cache`` (called by the entry points) leaves the
    directory to ``JAX_COMPILATION_CACHE_DIR`` when it is set, and falls
    back to the fixed in-checkout ``.jax_cache`` otherwise."""
    from repro.launch import compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(compile_cache.DEFAULT_DIR) == os.path.join(repo, ".jax_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(repo, "src"))
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x @ x)(jnp.ones((8, 8))).block_until_ready()\n")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == str(tmp_path)
    assert any(f.name.startswith("jit_") for f in tmp_path.iterdir())
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == str(compile_cache.DEFAULT_DIR)
