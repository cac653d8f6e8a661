"""Compile the main-path kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse — a
block not aligned to the (8, 128) tiling, more VMEM than a kernel may use, a
primitive Mosaic cannot lower.  Each test asserts the Mosaic kernel is in
the compiled program.  The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.search_space import seed_genome
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ops import DEFAULT_ATTN_GENOME
from repro.kernels.ssd import ssd_chunked


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
