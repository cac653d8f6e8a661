"""Compile the main-path kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse — a
block not aligned to the (8, 128) tiling, more VMEM than a kernel may use, a
primitive Mosaic cannot lower.  Each test asserts the Mosaic kernel is in
the compiled program.  The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import ARCHS
from repro.core.search_space import seed_genome
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ops import DEFAULT_ATTN_GENOME
from repro.kernels.ssd import ssd_chunked
from repro.launch.serve import make_serve_step
from repro.models import init_decode_cache, init_params


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16 = jnp.bfloat16


@pytest.mark.parametrize("batch,seq,causal,genome", [
    (8, 4096, True, "serve"),       # mha_causal_s4096, the models' genome
    (1, 32768, False, "serve"),     # mha_noncausal_s32768
    # the search's seed: kv_in_grid=False stages all of K and V in VMEM,
    # which needs more than Mosaic's default scoped VMEM at this length
    (1, 32768, False, "seed"),
])
def test_flash_attention_compiles_for_v5e(one_chip, batch, seq, causal,
                                          genome):
    kw = (seed_genome().kernel_kwargs() if genome == "seed"
          else dict(DEFAULT_ATTN_GENOME))
    qkv = ((batch, 16, seq, 128), BF16)
    _compile(functools.partial(flash_attention, causal=causal, **kw),
             one_chip, qkv, qkv, qkv)


def test_flash_attention_gqa_pack_compiles_for_v5e(one_chip):
    kw = dict(DEFAULT_ATTN_GENOME, gqa_pack=True)
    kv = ((8, 4, 4096, 128), BF16)
    _compile(functools.partial(flash_attention, causal=True, **kw),
             one_chip, ((8, 32, 4096, 128), BF16), kv, kv)


def test_flash_decode_compiles_at_qwen2_widths(one_chip):
    cache = ((8, 4, 8192, 128), BF16)
    _compile(flash_decode, one_chip, ((8, 28, 128), BF16), cache, cache,
             ((8,), jnp.int32))


def test_ssd_compiles_at_mamba2_780m_widths(one_chip):
    # 48 heads of P=64, N=128, chunk 256 (configs/mamba2_780m.py)
    B, L, H, P, N = 1, 2048, 48, 64, 128
    _compile(functools.partial(ssd_chunked, chunk=256, block_heads=8),
             one_chip, ((B, L, H, P), BF16), ((B, L, H), jnp.float32),
             ((H,), jnp.float32), ((B, L, 1, N), BF16), ((B, L, 1, N), BF16))


@pytest.mark.parametrize("kernel", ["flash_attention", "flash_decode"])
def test_kernel_custom_calls_carry_the_kernel_name(one_chip, kernel):
    """A trace names each kernel by its custom call's instruction name, and
    the benchmark finds the kernel by that name's prefix.  Called as the
    model calls it, inside its layer scopes, the name is still the kernel's."""
    def step(q, k, v, *valid_len):
        with jax.named_scope("attn"), jax.named_scope("kernel"):
            if valid_len:
                return ops.decode_attention(q, k, v, *valid_len, impl="pallas")
            return ops.attention(q, k, v, causal=True, impl="pallas")

    kv = ((2, 4, 1024, 128), BF16)
    shapes = ([((2, 8, 128), BF16), kv, kv, ((2,), jnp.int32)] if kernel == "flash_decode"
              else [((2, 8, 1024, 128), BF16), kv, kv])
    text = _compile(step, one_chip, *shapes)
    names = [m.group(1) for line in text.splitlines() if "tpu_custom_call" in line
             for m in [re.search(r"%([\w.\-]+) = .*custom-call\(", line)] if m]
    assert names and all(n.startswith(kernel) for n in names), names


V5E_HBM = 15.75 * 2**30          # what the compiler lets a v5e program plan


@pytest.mark.parametrize("sessions", [18, 32])
def test_qwen2_decode_step_updates_its_stacked_cache_in_place(one_chip, sessions):
    """qwen2-7b's decode step at its widths, 8 layers, against 16384 slots
    a session, bf16, the cache donated: the Mosaic ``flash_decode`` serves
    it, nothing copies a whole stacked K or V cache, and the plan fits one
    chip at 32 sessions (a step that rebuilt the stack needed it twice)."""
    cfg = dataclasses.replace(ARCHS["qwen2-7b"], n_layers=8, remat=False)
    slots = 16384

    def sds(tree, dtype=None):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(functools.partial(init_params, cfg),
                                jax.random.key(0)), BF16)
    cache = sds(jax.eval_shape(functools.partial(init_decode_cache, cfg, sessions,
                                                 slots)))
    step = jax.jit(make_serve_step(cfg, BF16, impl="pallas"), donate_argnums=(1,))
    compiled = step.lower(params, cache, jax.ShapeDtypeStruct(
        (sessions,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert re.search(r"%flash_decode[\w.]* = .*tpu_custom_call", text)
    stacked = f"bf16[{cfg.n_layers},{sessions},{cfg.n_kv_heads},{slots},{cfg.head_dim}]"
    whole = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"= " + re.escape(stacked) + r"\S* (copy|fusion)\(", line)]
    assert not whole, whole
    mem = compiled.memory_analysis()
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # the donated K and V (and the position) are the outputs' buffers
    assert mem.alias_size_in_bytes >= 2 * jnp.dtype(BF16).itemsize * (
        cfg.n_layers * sessions * cfg.n_kv_heads * slots * cfg.head_dim)
    assert planned < V5E_HBM
