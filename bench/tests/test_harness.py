"""The harness end to end on the CPU at a tiny size: it finds a cell that
was added as files alone, runs it, and judges it by the reference."""
import json

import pytest

from conftest import ROOT, make_root


@pytest.mark.parametrize("cell", ["tiny.prefill", "tiny.decode"])
def test_added_cell_runs_end_to_end(cpu_run, tmp_path, cell):
    root = make_root(tmp_path / "checkout")
    c = cpu_run.load_cell(cell, root)
    out = cpu_run.run_cell(c, seed=2**31 + 7, seconds=0.2, traced=False)
    kind = "prefill" if cell.endswith("prefill") else "decode"
    want = {"calls_in_window", "setup_s", f"{kind}_tokens_per_s"} | ({"decode_step_ms_p95"} if kind == "decode"
                                                     else set())
    assert set(out["metrics"]) == want
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)


def test_adding_a_cell_edits_no_file_of_the_benchmark(tmp_path):
    root = make_root(tmp_path / "checkout")
    for f in (ROOT / "bench").rglob("*"):
        if f.is_file() and "tests" not in f.parts and "__pycache__" not in f.parts:
            assert (root / f.relative_to(ROOT)).read_bytes() == f.read_bytes(), f
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("workloads", "configs", "end_to_end", "per_layer"):
        names = [e["name"] for e in new[key]]
        assert all(e["name"] in names for e in old[key])


def test_no_tpu_exits_non_zero():
    from bench import run
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.require_chip(1)


@pytest.mark.parametrize("with_program", [True, False])
def test_run_without_a_chip_prints_no_result(tmp_path, with_program):
    import os
    import shutil
    import subprocess
    import sys
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    if with_program:
        (tmp_path / "src").symlink_to(ROOT / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen2-7b.decode-long",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
