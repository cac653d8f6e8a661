"""Fixtures for the benchmark's own tests (``pytest bench/tests``).

They run on the CPU: the Pallas kernels interpret, and the harness's look
for a chip is replaced in the test.  No number they see is a device number.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY_CFG = {
    "name": "tiny", "source": "test", "family": "dense", "reference": "dense",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 2, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "qkv_bias": True, "mlp": "gated_silu", "norm": "rmsnorm", "norm_eps": 1e-6,
}
TINY_TRAFFIC = {
    "tiny-prefill": {"kind": "prefill", "batch": 2, "prompt_lens": [16, 32], "max_len": 64},
    "tiny-decode": {"kind": "decode", "sessions": 4, "max_len": 40, "start": 32,
                    "warmup_steps": 2},
}
# at this size sound runs read under 0.005 and the planted faults of
# test_faults.py over 0.15 (CPU, interpret mode)
TINY_LIMITS = {"tiny.prefill": {"rows": 2, "limits": {"token_gap": 0.02, "logit_err": 0.02}},
               "tiny.decode": {"limits": {"token_gap": 0.02, "logit_err": 0.02}}}
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def make_root(dst: Path, cfg=TINY_CFG) -> Path:
    """A checkout holding ``BENCHMARK.json``, the benchmark's directory and
    the program, with a tiny configuration, two traffic mixes, a prefill and
    a decode cell and an end-to-end metric added as new files and entries;
    no file of the benchmark is edited."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dst / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for name, mix in TINY_TRAFFIC.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, check in TINY_LIMITS.items():
        (dst / "bench" / "workloads" / f"{name}.json").write_text(json.dumps(check))
    bench["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for cell, mix in (("tiny.prefill", "tiny-prefill"), ("tiny.decode", "tiny-decode")):
        bench["workloads"].append({"name": cell, "config": "tiny", "traffic": mix,
                                   "chips": 1, "why": "test"})
    (dst / "bench" / "metrics" / "calls_in_window.py").write_text(
        "def read(run):\n    return float(len(run.calls))\n")
    bench["end_to_end"].insert(0, {"name": "calls_in_window", "unit": "calls",
                                   "better": "higher", "bound": 0.25, "source": "host_clock",
                                   "workloads": ["tiny.prefill", "tiny.decode"]})
    # the tiny cells report what the qwen2-7b cells of their kind report
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell, like in (("tiny.prefill", "qwen2-7b.prefill-long"),
                           ("tiny.decode", "qwen2-7b.decode-long")):
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """``run`` with its chip check replaced and the kernels interpreted; the
    compile cache goes to the test's own directory and is put back after."""
    import jax
    from bench import run
    from bench.families import dense
    monkeypatch.setattr(run, "require_chip", lambda chips, root: dict(CPU_DEVICE))
    monkeypatch.setattr(dense, "IMPL", "pallas_interpret")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    old = jax.config.jax_compilation_cache_dir
    yield run
    jax.config.update("jax_compilation_cache_dir", old)
