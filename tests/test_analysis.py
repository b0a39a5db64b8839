"""Static contract analyzer tests: every lint rule fires on a fixture it
must flag, the full analyzer is zero-findings on the real tree (no false
positives), and the launch verifier accepts every structure-zoo schedule
while rejecting deliberate corruptions for each kernel family."""
import dataclasses
import os
import textwrap

import numpy as np
import pytest

from repro.analysis import fingerprint_audit as fpa
from repro.analysis import lint_rules as lint
from repro.analysis import verify_launch as vl
from repro.analysis import workspace
from repro.core import bcsr as bcsr_lib
from repro.kernels import autotune, ops


def _src(text):
    return textwrap.dedent(text)


# =============================================================== lint rules
class TestLintFixtures:
    """Each rule must flag its fixture with a file:line diagnostic."""

    def test_traced_numpy_reachable(self):
        fs = lint.lint_source(_src("""
            import functools, jax
            import numpy as np
            @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
            def f(cfg, x):
                return helper(x)
            def f_fwd(cfg, x):
                return f(cfg, x), (x,)
            def f_bwd(cfg, res, g):
                return (g,)
            f.defvjp(f_fwd, f_bwd)
            def helper(x):
                return np.asarray(x) * 2
            """), "fix.py")
        assert [f.rule for f in fs] == ["traced-numpy"]
        assert fs[0].path == "fix.py" and fs[0].line > 0

    def test_traced_numpy_in_pallas_kernel(self):
        fs = lint.lint_source(_src("""
            import numpy as np
            import jax.experimental.pallas as pl
            def _kern(x_ref, o_ref):
                o_ref[...] = np.tanh(x_ref[...])
            def launch(x):
                return pl.pallas_call(_kern, out_shape=x)(x)
            """), "fix.py")
        assert [f.rule for f in fs] == ["traced-numpy"]

    def test_traced_numpy_float0_allowlisted_and_lru_boundary(self):
        fs = lint.lint_source(_src("""
            import functools, jax
            import numpy as np
            @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
            def f(cfg, x):
                return x + host(3)
            def f_fwd(cfg, x):
                return f(cfg, x), (x,)
            def f_bwd(cfg, res, g):
                z = jax.tree.map(
                    lambda t: np.zeros(t.shape, jax.dtypes.float0), res)
                return (g,)
            f.defvjp(f_fwd, f_bwd)
            @functools.lru_cache(maxsize=None)
            def host(n):
                return float(np.ones(n).sum())
            """), "fix.py")
        assert fs == []

    def test_lru_cache_unhashable_annotation(self):
        fs = lint.lint_source(_src("""
            import functools
            @functools.lru_cache(maxsize=None)
            def f(xs: list, d: int = 3):
                return sum(xs) + d
            """), "fix.py")
        assert [f.rule for f in fs] == ["lru-cache-static"]

    def test_lru_cache_mutable_default(self):
        fs = lint.lint_source(_src("""
            import functools
            @functools.lru_cache(maxsize=None)
            def f(n, xs=[]):
                return n
            """), "fix.py")
        assert [f.rule for f in fs] == ["lru-cache-static"]

    def test_lru_cache_unannotated_params_ok(self):
        """mlp_sparse_metas-style signatures (unannotated spec) pass."""
        fs = lint.lint_source(_src("""
            import functools
            @functools.lru_cache(maxsize=None)
            def f(spec, d: int, hints: tuple):
                return (spec, d, hints)
            """), "fix.py")
        assert fs == []

    def test_custom_vjp_missing_defvjp(self):
        fs = lint.lint_source(_src("""
            import jax
            @jax.custom_vjp
            def f(x):
                return x
            """), "fix.py")
        assert [f.rule for f in fs] == ["custom-vjp-pairing"]

    def test_custom_vjp_bad_bwd_arity(self):
        fs = lint.lint_source(_src("""
            import functools, jax
            @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
            def f(a, b, x, y):
                return x
            def f_fwd(a, b, x, y):
                return f(a, b, x, y), (x,)
            def f_bwd(a, b, res, g):
                return (g,)
            f.defvjp(f_fwd, f_bwd)
            """), "fix.py")
        assert [f.rule for f in fs] == ["custom-vjp-pairing"]
        assert "cotangent" in fs[0].message

    def test_custom_vjp_computed_return_skipped(self):
        """_attn_fused_bwd-style ``return vjp(g)`` must not be flagged."""
        fs = lint.lint_source(_src("""
            import functools, jax
            @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
            def f(cfg, x, y):
                return x
            def f_fwd(cfg, x, y):
                return f(cfg, x, y), (x, y)
            def f_bwd(cfg, res, g):
                vjp = res[0]
                return vjp(g)
            f.defvjp(f_fwd, f_bwd)
            """), "fix.py")
        assert fs == []

    def test_static_aux_not_frozen(self):
        fs = lint.lint_source(_src("""
            import dataclasses
            @dataclasses.dataclass
            class FooMeta:
                n: int
            """), "fix.py")
        assert [f.rule for f in fs] == ["static-aux-frozen"]

    def test_static_aux_unhashable_field(self):
        fs = lint.lint_source(_src("""
            import dataclasses
            @dataclasses.dataclass(frozen=True)
            class FooSpec:
                xs: list
            """), "fix.py")
        assert [f.rule for f in fs] == ["static-aux-frozen"]

    def test_static_aux_frozen_ok_and_name_scope(self):
        fs = lint.lint_source(_src("""
            import dataclasses
            @dataclasses.dataclass(frozen=True)
            class FooMeta:
                n: int
            @dataclasses.dataclass
            class ScratchBuffer:
                xs: list
            """), "fix.py")
        assert fs == []

    def test_fingerprint_missing_meta_field(self):
        fs = lint.check_fingerprint_fields(
            _src("""
                import dataclasses
                @dataclasses.dataclass(frozen=True)
                class SparseMeta:
                    nnzb: int
                    max_bpr: int
                """),
            _src("""
                import dataclasses
                @dataclasses.dataclass(frozen=True)
                class Fingerprint:
                    nnzb: int
                    def key(self):
                        return f"v6|nnzb={self.nnzb}"
                def fingerprint(meta, n):
                    return Fingerprint(nnzb=meta.nnzb)
                """))
        assert [f.rule for f in fs] == ["fingerprint-fields"]
        assert "max_bpr" in fs[0].message

    def test_fingerprint_field_not_in_key(self):
        fs = lint.check_fingerprint_fields(
            _src("""
                import dataclasses
                @dataclasses.dataclass(frozen=True)
                class SparseMeta:
                    nnzb: int
                """),
            _src("""
                import dataclasses
                @dataclasses.dataclass(frozen=True)
                class Fingerprint:
                    nnzb: int
                    orphan: int
                    def key(self):
                        return f"v6|nnzb={self.nnzb}"
                def fingerprint(meta, n):
                    return Fingerprint(nnzb=meta.nnzb, orphan=0)
                """))
        assert [f.rule for f in fs] == ["fingerprint-fields"]
        assert "orphan" in fs[0].message


def test_lint_tree_zero_findings_on_src():
    """No false positives: the current tree satisfies every invariant."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    findings = lint.lint_tree(root)
    assert findings == [], "\n".join(str(f) for f in findings)


# =========================================================== launch verifier
def _rand_case():
    a = bcsr_lib.random_bcsr_exact(0, (256, 256), (16, 16), 64)
    return a, ops.prepare_sparse_meta(a)


class TestVerifier:
    def test_zoo_all_clean(self):
        findings = vl.run_verify()
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_attn_corruption_rejected(self):
        """The fused-attention schedule (``models.attention._fused_inputs``
        builds it with ``bcsr_attn.static_schedule``) under the attn
        family: dropped slot, sentinel-on-live and a wrong column all
        rejected."""
        from repro.core.attention_mask import banded
        from repro.models import attention as A
        spec, seq = banded(32), 128
        a = A.attention_mask_bcsr(spec, seq, (16, 16))
        _, fi, fc, meta = A._fused_inputs(spec, seq, (16, 16))
        assert vl.verify_schedule("attn", fi, fc, a.row_ids, a.col_ids,
                                  meta) == []
        live = np.flatnonzero(fi != meta.nnzb)
        bad = fi.copy()
        bad[live[0]] = meta.nnzb          # sentinel on a live block
        assert vl.verify_schedule("attn", bad, fc, a.row_ids, a.col_ids,
                                  meta)
        bad = fi.copy()
        bad[live[1]] = int(fi[live[0]])   # slot dropped (duplicated twin)
        assert vl.verify_schedule("attn", bad, fc, a.row_ids, a.col_ids,
                                  meta)
        # wrong column on a live slot: the kernel would read the wrong
        # K/V panel
        bad_fc = fc.copy()
        bad_fc[live[0]] = (bad_fc[live[0]] + 1) % meta.n_block_cols
        errs = vl.verify_schedule("attn", fi, bad_fc, a.row_ids, a.col_ids,
                                  meta)
        assert errs and any("col" in e for e in errs)

    def test_meta_invariants(self):
        _, meta = _rand_case()
        assert vl.verify_meta(meta) == []
        assert vl.verify_meta(dataclasses.replace(meta, nnzb=meta.nnzb * 100))
        assert vl.verify_meta(dataclasses.replace(meta, nnzb_t=meta.nnzb - 1))
        assert vl.verify_meta(
            dataclasses.replace(meta, max_bpr=meta.n_block_cols + 1))

    def test_sharded_meta_invariants(self):
        from repro.launch import dist_spmm
        a = bcsr_lib.random_bcsr_exact(7, (320, 256), (16, 16), 80)
        smeta = dist_spmm.prepare_sharded_meta(a, 4)
        assert vl.verify_sharded_meta(smeta) == []
        bad = dataclasses.replace(smeta,
                                  nnzb_t_per_shard=smeta.nnzb_t_per_shard - 1)
        assert vl.verify_sharded_meta(bad)
        bad = dataclasses.replace(smeta, rows_per_shard=1)
        assert vl.verify_sharded_meta(bad)

    def test_dims_only_meta_tolerated_but_not_schedulable(self):
        from repro.core.sparse_linear import SparsitySpec, sparse_linear_specs
        _, meta = sparse_linear_specs(
            96, 64, SparsitySpec(density=0.3, block=(16, 16)))
        assert meta.max_bpr == 0
        assert vl.verify_meta(meta) == []     # dims-only budgets are legal
        # but the fused attention needs a schedule bound
        assert vl.verify_launch(meta, "fused", n=64, op="attn")
        assert vl.verify_launch(meta, "xla", n=64) == []

    def test_vmem_budget(self):
        _, meta = _rand_case()
        # the fused attention: running state and one K/V cell
        assert vl.verify_launch(meta, "fused", n=64, op="attn") == []
        errs = vl.verify_launch(meta, "fused", n=64, op="attn",
                                vmem_budget=1024)
        assert errs and any("VMEM" in e for e in errs)
        # the pallas kernel: accumulator, output tile and DMA ring
        assert vl.verify_launch(meta, "pallas", n=512) == []
        errs = vl.verify_launch(meta, "pallas", n=512, vmem_budget=1024)
        assert errs and any("VMEM" in e for e in errs)
        errs = vl.verify_launch(meta, "pallas", n=8192, bn=8192)
        assert errs and any("VMEM" in e for e in errs)
        # the pallas SDDMM: X panel, Y ring and output ring; a long
        # contraction is chunked, so it stays inside the budget
        assert vl.verify_launch(meta, "pallas", n=512, op="sddmm") == []
        errs = vl.verify_launch(meta, "pallas", n=512, op="sddmm",
                                vmem_budget=1024)
        assert errs and any("VMEM" in e for e in errs)
        assert vl.verify_launch(meta, "pallas", n=65536, op="sddmm") == []

    def test_sddmm_stream_vmem_estimate(self):
        from repro.kernels import bcsr_spmm as pk
        _, meta = _rand_case()
        h, w = meta.block
        # N = 100 is padded to one 128-lane chunk; bn plays no part
        want = pk.sddmm_stream_vmem_bytes(h, w, 128, meta.n_block_rows,
                                          meta.nnzb)
        for bn in (128, 512):
            assert vl.estimate_vmem_bytes(meta, "sddmm_stream", 100,
                                          bn) == want
        # HPCG 64^3 at N = 512: X panel 2 x 128 KB, 16 Y slabs of 128 KB,
        # 16 output blocks of 4 KB
        assert pk.sddmm_stream_vmem_bytes(16, 128, 512, 16384, 95760) == \
            2 * 128 * 512 * 2 + 16 * (128 * 512 + 16 * 128) * 2
        # the MoE's gate/up at D = 2,048: 8 slabs of 512 KB
        assert pk.sddmm_stream_vmem_bytes(128, 128, 2048, 1600, 17600) == \
            2 * 128 * 2048 * 2 + 8 * (128 * 2048 + 128 * 128) * 2

    def test_chunk_schedule_invariants(self):
        """Overlap schedules: every builder output passes; every corrupted
        schedule (gap, overlap, empty chunk, wrong span) is caught."""
        from repro.launch.dist_spmm import chunk_schedule
        for n in (1, 7, 64, 512):
            for k in (1, 2, 4, 8):
                assert vl.verify_chunk_schedule(
                    chunk_schedule(n, k), n, block=(16, 16)) == []
        # overlap: column range accumulated twice -> not bit-identical
        errs = vl.verify_chunk_schedule([(0, 3), (2, 6), (6, 10)], 10)
        assert errs and "overlap" in errs[0]
        # gap: columns dropped from the output panel
        errs = vl.verify_chunk_schedule([(0, 3), (4, 10)], 10)
        assert errs and "gap" in errs[0]
        # empty / descending chunk
        assert vl.verify_chunk_schedule([(0, 6), (6, 6), (6, 10)], 10)
        assert vl.verify_chunk_schedule([(0, 8), (8, 7)], 10)
        # wrong span at either end
        errs = vl.verify_chunk_schedule([(1, 6), (6, 9)], 10)
        assert len(errs) == 2
        assert vl.verify_chunk_schedule([], 10)
        assert vl.verify_chunk_schedule("nope", 10)
        # per-chunk VMEM gate fires under a tiny budget
        errs = vl.verify_chunk_schedule(
            chunk_schedule(512, 4), 512, block=(16, 16), vmem_budget=1024)
        assert errs and all("VMEM" in e for e in errs)

    def test_resolve_backend_hook(self, monkeypatch):
        a, meta = _rand_case()
        monkeypatch.setenv("REPRO_VERIFY_LAUNCH", "1")
        assert ops.resolve_backend("pallas", 512, meta, 64) == \
            ("pallas", 512)
        bad = dataclasses.replace(meta, nnzb=meta.nnzb * 100)
        with pytest.raises(vl.LaunchError):
            ops.resolve_backend("pallas", 512, bad, 64)
        monkeypatch.delenv("REPRO_VERIFY_LAUNCH")
        ops.resolve_backend("pallas", 512, bad, 64)   # opt-in: no check


# ======================================================== shared estimators
def test_workspace_matches_benchmark_formulas():
    """The unified estimator must reproduce the exact expressions the
    attention benchmark baseline pinned (satellite: dedupe, not change)."""
    _, meta = _rand_case()
    h, w = meta.block
    assert workspace.attn_composed_workspace_bytes(meta) == \
        2 * meta.nnzb * h * w * 4
    for d in (64, 128, 256):
        dpad = max(-(-d // 128), 1) * 128
        assert workspace.attn_fused_state_bytes((16, 16), d) == \
            16 * (2 * 128 + dpad) * 4


def test_workspace_matches_pick_bn_feasibility():
    """``fits_vmem`` is the same predicate ``autotune.pick_bn`` budgets
    with: every candidate pick_bn accepts, fits_vmem accepts, and
    vice versa — the estimator and the autotuner cannot drift."""
    candidates = (128, 256, 512, 1024, 2048, 8192, 65536)
    for block in ((16, 16), (32, 32), (128, 128)):
        _, meta = _rand_case()
        meta = dataclasses.replace(meta, block=block)
        for n in (128, 512, 4096):
            bn = autotune.pick_bn(meta, n, candidates)
            feasible = [c for c in candidates
                        if workspace.fits_vmem(block, c)]
            if feasible:
                assert workspace.fits_vmem(block, bn)
                assert bn == max(c for c in feasible
                                 if c <= max(n, min(feasible)))


def test_dryrun_attention_report_uses_shared_estimator():
    import repro.configs as C
    from repro.launch import dryrun
    cfg = C.get_config("smat-attn-1.3b:smoke")
    rep = dryrun.sparse_attention_report(cfg, seq_len=64)
    assert rep["verify"]["ok"], rep["verify"]
    spec = cfg.attn_sparsity
    from repro.models import attention as A
    seq = max(64, spec.block[0] * 2)
    meta = A.attention_mask_meta(spec.mask, seq, spec.block)
    assert rep["composed_workspace_bytes"] == \
        workspace.attn_composed_workspace_bytes(meta)
    assert rep["fused_state_bytes"] == \
        workspace.attn_fused_state_bytes(spec.block, cfg.head_dim)


# ========================================================= fingerprint audit
class TestFingerprintAudit:
    def test_round_trip(self):
        _, meta = _rand_case()
        for op in ("spmm", "sddmm", "attn"):
            fp = autotune.fingerprint(meta, 512, op=op)
            assert fpa.parse_key(fp.key()) == fp

    def test_stale_version_actionable(self):
        fp = autotune.fingerprint(_rand_case()[1], 512)
        stale = "v5" + fp.key()[2:]
        with pytest.raises(fpa.StaleKeyError) as ei:
            fpa.parse_key(stale)
        msg = str(ei.value)
        assert "v5" in msg and "v7" in msg and "refresh" in msg
        # the immediately-previous grammar (no nk= field) is stale too
        with pytest.raises(fpa.StaleKeyError):
            fpa.parse_key("v6" + fp.key()[2:].rsplit("|nk=", 1)[0])

    def test_malformed_key_rejected(self):
        with pytest.raises(ValueError):
            fpa.parse_key("v7|op=spmm|nbr=oops")
        with pytest.raises(ValueError):
            fpa.parse_key("not a key at all")

    def test_injectivity_over_sampled_space(self):
        assert fpa.audit_injectivity() == []

    def test_committed_artifacts_parse(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        findings = fpa.audit_files(root)
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_stale_cache_file_flagged(self, tmp_path, monkeypatch):
        fp = autotune.fingerprint(_rand_case()[1], 512)
        cache = tmp_path / "cache.json"
        cache.write_text(
            '{"version": 1, "entries": {"v5%s": '
            '{"variant": "nnz_stream", "bn": 512}}}' % fp.key()[2:])
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
        findings = fpa.audit_files(str(tmp_path))
        assert findings and all(f.rule == "fingerprint-audit"
                                for f in findings)


# ===================================================================== CLI
def test_cli_all_green_on_current_tree():
    from repro.analysis.__main__ import main
    assert main(["--all"]) == 0


def test_cli_nonzero_with_diagnostics_on_bad_tree(tmp_path, capsys):
    from repro.analysis.__main__ import main
    bad = tmp_path / "bad.py"
    bad.write_text(_src("""
        import dataclasses
        @dataclasses.dataclass
        class BadMeta:
            n: int
        """))
    rc = main(["--lint", "--src", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"{bad}:" in out and "[static-aux-frozen]" in out
