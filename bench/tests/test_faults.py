"""A whole run on the CPU with the timed path broken underneath: ``correct``
has to come out false for each fault a serving cell can have.  (One chip:
no exchange between chips to leave out.)"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import dense
from conftest import ROOT, make_root
from repro.launch.serve import make_serve_step

_prefill, _decode = dense.prefill_fn, dense.decode_fn


def _kept(b: int, layout: str) -> np.ndarray:
    """For each row of a batch of ``b``, the row whose answer it gets when
    half of the batch is left out: the first half kept and copied over the
    second, or the even rows kept and copied over the odd."""
    return np.arange(b) % (b // 2) if layout == "first-half" else np.arange(b) // 2 * 2


def half_batch(arch, max_len, layout="first-half"):
    """Half of the batch left out: its rows copied from the other half."""
    f = _prefill(arch, max_len)

    def run(params, tokens):
        logits, _, cache = f(params, tokens)
        logits = logits[_kept(logits.shape[0], layout)]
        return logits, jnp.argmax(logits, -1).astype(jnp.int32), cache
    return run


def decode_half_batch(arch):
    """Half of the sessions left out: their tokens and logits copied from
    the other half."""
    f = _decode(arch)

    def run(params, cache, token):
        tok, top, cache = f(params, cache, token)
        keep = _kept(tok.shape[0], "first-half")
        return tok[keep], top[keep], cache
    return run


def prefill_token_altered(arch, max_len):
    f = _prefill(arch, max_len)

    def run(params, tokens):
        logits, tok, cache = f(params, tokens)
        return logits, (tok + 1) % arch.vocab_size, cache
    return run


def state_unchanged(arch):
    """The step returns the cache it was given: no key or value written,
    the position not advanced."""
    step = make_serve_step(arch, jnp.bfloat16, impl=dense.IMPL)

    def run(params, cache, token):
        logits, _ = step(params, cache, token)
        return jnp.argmax(logits, -1).astype(jnp.int32), jnp.max(logits, -1), cache
    return jax.jit(run)


def decode_token_altered(arch):
    f = _decode(arch)

    def run(params, cache, token):
        tok, top, cache = f(params, cache, token)
        return (tok + 1) % arch.vocab_size, top, cache
    return run


@pytest.mark.parametrize("cell,entry,fault", [
    ("tiny.prefill", "prefill_fn", half_batch),
    ("tiny.prefill", "prefill_fn", prefill_token_altered),
    ("tiny.decode", "decode_fn", state_unchanged),
    ("tiny.decode", "decode_fn", decode_token_altered),
    ("tiny.decode", "decode_fn", decode_half_batch),
], ids=["prefill-half-batch", "prefill-token", "decode-state-unchanged", "decode-token",
        "decode-half-batch"])
def test_fault_is_not_correct(cpu_run, tmp_path, monkeypatch, cell, entry, fault):
    root = make_root(tmp_path / "checkout")
    monkeypatch.setattr(dense, entry, fault)
    out = cpu_run.run_cell(cpu_run.load_cell(cell, root), seed=3, seconds=0.1, traced=False)
    assert out["correct"] is False, out["checks"]


def _prefill_cells() -> list[tuple[int, int]]:
    """(batch, rows compared) of every prefill cell of the benchmark."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = []
    for w in bench["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        if mix["kind"] == "prefill":
            check = json.loads((ROOT / "bench" / "workloads" / f"{w['name']}.json").read_text())
            out.append((mix["batch"], check["rows"]))
    return out


@pytest.mark.parametrize("batch,rows", _prefill_cells())
def test_every_prefill_cell_compares_more_than_half_of_a_batch(batch, rows):
    assert batch // 2 < rows <= batch


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("layout", ["first-half", "even-rows"])
@pytest.mark.parametrize("batch,rows", [c for c in _prefill_cells() if c[0] > 1])
def test_half_batch_is_caught_at_the_cells_own_sampling(cpu_run, tmp_path, monkeypatch,
                                                        batch, rows, layout, seed):
    """The tiny cell with a real cell's batch and number of rows compared:
    whichever rows the seed draws, a half of the batch left out shows."""
    root = make_root(tmp_path / "checkout")
    mix_file = root / "bench" / "traffic" / "tiny-prefill.json"
    mix = json.loads(mix_file.read_text())
    mix_file.write_text(json.dumps(dict(mix, batch=batch, prompt_lens=[16])))
    check_file = root / "bench" / "workloads" / "tiny.prefill.json"
    check_file.write_text(json.dumps(dict(json.loads(check_file.read_text()), rows=rows)))
    monkeypatch.setattr(dense, "prefill_fn",
                        lambda arch, max_len: half_batch(arch, max_len, layout))
    out = cpu_run.run_cell(cpu_run.load_cell("tiny.prefill", root), seed=seed, seconds=0.1,
                           traced=False)
    assert out["correct"] is False, out["checks"]
