"""The model step's layer scopes, read from the op-name metadata of the
compiled prefill and decode step of a tiny dense model (CPU).

The scopes (``models/transformer.py``) are what a profiler trace of the step
charges device time to, so each op has to land under the layer it belongs
to: projections and MLP matmuls under their sublayer, the decode step's new
key and value under ``kv_cache``, and the scan's slicing of the layer stack
under ``layers`` and no sublayer.  The decode step's layout test reads the
same text for what the step writes into the stacked cache it carries."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, Block
from repro.models import init_decode_cache, init_params
from repro.models.transformer import decode_step, prefill

SCOPES = {"embed", "layers", "attn", "qkv", "kernel", "kv_cache", "out", "mlp", "moe",
          "ssm", "head"}
B, S, MAX_LEN = 2, 16, 32
CFG = ArchConfig(
    name="tiny-scopes", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, vocab_size=256, pattern=(Block(kind="attn", mlp="gated_silu"),),
    qkv_bias=True, rope_theta=1e4, norm="rmsnorm", norm_eps=1e-6, tie_embeddings=False,
    remat=False)
_INSTR = re.compile(r"%[\w.\-]+ = (\S+) ([\w\-]+)\(.*op_name=\"([^\"]*)\"")


def _ops(text):
    """[(result shape, opcode, scope path)] of every instruction, fused
    ones included, whose metadata names an op."""
    out = []
    for line in text.splitlines():
        m = _INSTR.search(line)
        if m:
            out.append((m.group(1).split("{")[0], m.group(2), _scopes(m.group(3))))
    return out


def _scopes(op_name):
    return "/".join(p for p in op_name.split(";")[0].split("/") if p in SCOPES)


def _dims(shape):
    return tuple(int(d) for d in shape.split("[", 1)[1].rstrip("]").split(",") if d)


@pytest.fixture(scope="module")
def compiled():
    params = jax.eval_shape(functools.partial(init_params, CFG), jax.random.key(0))
    cache = jax.eval_shape(functools.partial(init_decode_cache, CFG, B, MAX_LEN))
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pre = jax.jit(lambda p, t: prefill(p, CFG, t, MAX_LEN, impl="blocked"))
    dec = jax.jit(lambda p, c, t: decode_step(p, CFG, c, t, impl="blocked"))
    return {
        "prefill": _ops(pre.lower(params, jax.ShapeDtypeStruct((B, S), jnp.int32))
                        .compile().as_text()),
        "decode": _ops(dec.lower(params, cache, tok).compile().as_text()),
        "stacked": {tuple(x.shape[1:]) for x in jax.tree_util.tree_leaves(
            (params["dec"], cache["layers"]))},
    }


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_every_matmul_sits_in_its_sublayer(compiled, step):
    where = {scopes for _, op, scopes in compiled[step] if op == "dot"}
    # the CPU's attention computes with matmuls of its own, in the kernel's scope
    assert where <= {"layers/attn/qkv", "layers/attn/kernel", "layers/attn/out",
                     "layers/mlp", "head"}
    assert {"layers/attn/qkv", "layers/attn/out", "layers/mlp", "head"} <= where


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_the_scan_slices_the_layer_stack_under_layers(compiled, step):
    slices = [(_dims(shape), scopes) for shape, op, scopes in compiled[step]
              if op == "dynamic-slice" and _dims(shape)[:1] == (1,)
              and _dims(shape)[1:] in compiled["stacked"]]
    assert slices
    assert {scopes for _, scopes in slices} == {"layers"}
    if step == "decode":          # the stacked K and V caches, one layer each
        cache = (1, B, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)
        assert sum(dims == cache for dims, _ in slices) >= 2


def test_the_decode_step_writes_the_new_key_and_value_under_kv_cache(compiled):
    layer_cache = (B, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)
    writes = [scopes for shape, op, scopes in compiled["decode"]
              if op == "dynamic-update-slice" and _dims(shape) == layer_cache]
    assert writes == ["layers/attn/kv_cache"] * 2
    # and, one row each, into the stacked cache the layer loop carries
    stacked = (CFG.n_layers, *layer_cache)
    rows = [scopes for shape, op, scopes in compiled["decode"]
            if op == "dynamic-update-slice" and _dims(shape) == stacked]
    assert rows == ["layers/attn/kv_cache"] * 2
    # prefill arranges its K and V into the decode cache under kv_cache too
    arranged = (CFG.n_layers, B, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)
    pads = {scopes for shape, op, scopes in compiled["prefill"]
            if op == "pad" and _dims(shape) == arranged}
    assert pads == {"kv_cache"}


_ARRAY = re.compile(r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(([^)]*)\)")
_ALIAS = re.compile(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)")


def test_the_donated_decode_cache_is_written_in_place():
    """The stacked cache is the layer loop's carry: with the cache donated,
    nothing copies it or rebuilds it from per-layer caches; the only writes
    whose output is a whole stack put one token row into it, under
    ``layers/attn/kv_cache``; and every donated leaf is its output's buffer.
    Three layers, so no per-layer shape equals a stacked one, and a float32
    cache: the CPU widens a bfloat16 update around the whole buffer, which a
    TPU does not (``test_chip_compile`` holds the bfloat16 step there)."""
    cfg = dataclasses.replace(CFG, n_layers=3)
    params = jax.eval_shape(functools.partial(init_params, cfg), jax.random.key(0))
    cache = jax.eval_shape(functools.partial(init_decode_cache, cfg, B, MAX_LEN,
                                             cache_dtype=jnp.float32))
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t, impl="blocked"),
                   donate_argnums=(1,))
    text = step.lower(params, cache, jax.ShapeDtypeStruct((B,), jnp.int32)) \
        .compile().as_text()

    shape_of, scope_of, instrs = {}, {}, []
    for line in text.splitlines():
        m = _ARRAY.search(line)
        if m:
            name, shape, op, operands = m.groups()
            shape_of[name] = _dims(shape)
            meta = _INSTR.search(line)
            scope_of[name] = _scopes(meta.group(3)) if meta else None
            instrs.append((name, op, operands))

    stacked = (cfg.n_layers, B, cfg.n_kv_heads, MAX_LEN, cfg.head_dim)
    row = (1, B, cfg.n_kv_heads, 1, cfg.head_dim)
    plumbing = {"parameter", "get-tuple-element", "bitcast", "fusion"}
    writes = [(name, op, operands) for name, op, operands in instrs
              if shape_of[name] == stacked and op not in plumbing]
    assert writes and {op for _, op, _ in writes} == {"dynamic-update-slice"}, writes
    for name, _, operands in writes:
        update = operands.split(", ")[1].lstrip("%")
        assert shape_of[update] == row, (name, shape_of[update])
        assert scope_of[name] == "layers/attn/kv_cache", name

    n_params = len(jax.tree_util.tree_leaves(params))
    n_cache = len(jax.tree_util.tree_leaves(cache))
    header = text.splitlines()[0]
    aliases = {int(o): int(p) for o, p in _ALIAS.findall(header.split("entry_computation")[0])}
    # outputs: (logits, cache leaves...); inputs: (params..., cache leaves..., token)
    assert aliases == {1 + i: n_params + i for i in range(n_cache)}
