"""``BENCHMARK.json`` against the benchmark's contract, and discovery: a
cell, a configuration and a per-layer metric are added by new files and
entries alone."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = bench.load_benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    for p in BENCH["paths"]:
        assert (bench.REPO / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_entries():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (bench.REPO / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = bench.load_cell(cell)
    assert bench.driver(c).setup
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    readers = bench.metric_readers(c)
    assert readers and all(callable(r.read) for r in readers.values())
    for m in c.per_layer:          # each reports what its metric moves
        assert m["moves"] in names


def _copy(tmp_path) -> Path:
    repo = tmp_path / "repo"
    shutil.copytree(bench.PKG, repo / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench.REPO / "BENCHMARK.json", repo / "BENCHMARK.json")
    return repo


def _digest(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file()}


def test_new_cell_config_and_metric_by_files_alone(tmp_path):
    repo = _copy(tmp_path)
    before = _digest(repo)
    (repo / "chipbench/generators/laplace_5pt.py").write_text(
        "import scipy.sparse as sp\n"
        "def matrix(config):\n"
        "    n = config['nx'] * config['ny']\n"
        "    return sp.identity(n, format='csr', dtype='float32') * 4\n")
    cfg = json.loads((repo / "chipbench/configs/hpcg.json").read_text())
    cfg.update(name="laplace", generator="laplace_5pt", nx=64, ny=64)
    (repo / "chipbench/configs/laplace.json").write_text(json.dumps(cfg))
    (repo / "chipbench/traffic/spmm-n16.json").write_text(json.dumps(
        {"driver": "spmm_loop", "op": "spmm", "n": 16, "panels": 2,
         "backend": "auto", "sample": 1}))
    (repo / "chipbench/metrics/calls_per_s.lib.py").write_text(
        "def read(ctx):\n"
        "    return ctx.window['calls'] / ctx.window['seconds']\n")
    b = json.loads((repo / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "laplace", "source": "x",
                         "file": "chipbench/configs/laplace.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "laplace.spmm-n16",
                           "config": "laplace", "traffic": "spmm-n16",
                           "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if m["name"] == "lib_gflops":
            m["workloads"].append("laplace.spmm-n16")
    b["per_layer"].append({"name": "calls_per_s.lib", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "kernels", "moves": "lib_gflops",
                           "workloads": ["laplace.spmm-n16"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(b))

    after = _digest(repo)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {Path("BENCHMARK.json")}      # entries, no edits

    cell = bench.load_cell("laplace.spmm-n16", repo=repo)
    assert cell.config["nx"] == 64 and cell.traffic["n"] == 16
    assert bench.driver(cell).__file__.startswith(str(repo))
    assert bench.generator(cell).matrix(cell.config).shape == (4096, 4096)
    assert {m["name"] for m in cell.end_to_end} == {"lib_gflops", "setup_s"}
    ctx = SimpleNamespace(window={"calls": 30, "seconds": 3.0}, trace=None,
                          setup={}, peak=None, cell=cell)
    assert bench.read_metrics(cell, ctx) == {"calls_per_s.lib": 10.0}
    old = bench.load_cell("hpcg.spmm-n8", repo=repo)
    assert "calls_per_s.lib" not in bench.metric_readers(old)


def _python(cwd, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


RUN = ("chipbench/run.py", "--workload", "hpcg.spmm-n8", "--seed", "1",
       "--seconds", "1", "--trace", "0")


def test_refuses_a_cpu():
    p = _python(bench.REPO, *RUN)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files gives
    no result: the run stops, and the set-up finds no program to run."""
    repo = _copy(tmp_path)
    p = _python(repo, *RUN)
    assert p.returncode != 0 and p.stdout == ""
    setup = ("import sys; sys.path[:0] = ['.']\n"
             "from chipbench import bench\n"
             "cell = bench.load_cell('hpcg.spmm-n8')\n"
             "bench.driver(cell).setup(cell, 1, None)\n")
    p = _python(repo, "-c", setup)
    assert p.returncode != 0 and "No module named 'repro'" in p.stderr
