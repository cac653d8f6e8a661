"""Jit'd dispatch wrappers over the Pallas kernels and their jnp fallbacks.

Implementation selection:
  "pallas"            pl.pallas_call, Mosaic lowering (TPU runtime)
  "pallas_interpret"  pl.pallas_call, interpret=True (CPU kernel validation)
  "blocked"           pure-jnp online-softmax scan (CPU / 512-device dry-run —
                      Mosaic cannot lower on the CPU backend, and the blocked
                      path is memory-safe at 32k+; identical math, identical
                      FLOPs for the roofline)
  "naive"             full score matrix (tiny shapes / tests only)
  "auto"              pallas on TPU backend, blocked otherwise

The active attention genome (``core.search_space.KernelGenome``) is passed as
a plain dict of kernel kwargs so models stay decoupled from the search code.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import axis_size, batch_axes, div_axis, get_mesh
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.ssd import ssd_chunked as _ssd_kernel

_DEFAULT_IMPL = os.environ.get("REPRO_KERNEL_IMPL", "auto")

DEFAULT_ATTN_GENOME = dict(
    block_q=128, block_k=128, rescale_mode="branchless",
    mask_mode="block_skip", div_mode="deferred", kv_in_grid=True,
    acc_dtype="f32",
)


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = impl


def resolve_impl(impl: Optional[str] = None) -> str:
    impl = impl or _DEFAULT_IMPL
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "blocked"
    return impl


def _per_shard(kernel, *operands, n_heads: int, n_kv_heads: int):
    """Run a Pallas ``kernel`` on each device's share of its operands.

    The compiler cannot partition a custom call, so under an active mesh
    the call goes through ``shard_map``: batch rows over the batch axes and
    heads over the model axis, each where they divide.  Every operand and
    the output lead with (batch, heads) — the rank-1 ``valid_len`` with
    batch alone.  Without a mesh the kernel runs as it is."""
    mesh = get_mesh()
    if mesh is None:
        return kernel(*operands)
    ba = batch_axes()
    n_batch = 1
    for a in ba:
        n_batch *= axis_size(a)
    rows = ba if ba and operands[0].shape[0] % n_batch == 0 else None
    heads = "model" if div_axis(n_heads) and div_axis(n_kv_heads) else None

    def spec(x):
        return P(rows) if x.ndim == 1 else P(rows, heads, *([None] * (x.ndim - 2)))

    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=tuple(spec(x) for x in operands),
                         out_specs=spec(operands[0]), check_vma=False)(*operands)


def attention(
    q: jnp.ndarray,               # (B, Hq, Sq, D)
    k: jnp.ndarray,               # (B, Hkv, Sk, D)
    v: jnp.ndarray,               # (B, Hkv, Sk, D)
    *,
    causal: bool = False,
    window: Optional[int] = None,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,
    impl: Optional[str] = None,
    genome: Optional[dict] = None,
) -> jnp.ndarray:
    impl = resolve_impl(impl)
    g = dict(DEFAULT_ATTN_GENOME, **(genome or {}))
    if impl in ("pallas", "pallas_interpret"):
        assert q_offset == 0, "prefill kernel assumes aligned q/k positions"

        def kernel(q, k, v):
            return _flash(
                q, k, v, causal=causal, window=window, softcap=softcap,
                scale=scale, interpret=(impl == "pallas_interpret"), **g)

        return _per_shard(kernel, q, k, v, n_heads=q.shape[1],
                          n_kv_heads=k.shape[1])
    if impl == "blocked":
        # causal SWA with a band narrower than the sequence: the q-chunked
        # banded path skips dead key blocks entirely (flops AND bytes)
        Sq, Sk = q.shape[2], k.shape[2]
        cq = min(2048, Sq)
        if (causal and window is not None and q_offset == 0 and Sq == Sk
                and Sq % cq == 0 and window + cq < Sk):
            return _ref.flash_reference_banded(
                q, k, v, window=window, softcap=softcap, scale=scale,
                chunk_q=cq)
        return _ref.flash_reference_blocked(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            block_k=max(512, g["block_k"]), q_offset=q_offset)
    if impl == "naive":
        return _ref.mha_reference(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
            q_offset=q_offset)
    raise ValueError(f"unknown impl {impl!r}")


def decode_attention(
    q: jnp.ndarray,               # (B, Hq, D)
    k_cache: jnp.ndarray,         # (B, Hkv, L, D)
    v_cache: jnp.ndarray,         # (B, Hkv, L, D)
    valid_len: jnp.ndarray,       # (B,)
    *,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    genome: Optional[dict] = None,
) -> jnp.ndarray:
    impl = resolve_impl(impl)
    g = dict(DEFAULT_ATTN_GENOME, **(genome or {}))
    if impl in ("pallas", "pallas_interpret"):
        def kernel(q, k_cache, v_cache, valid_len):
            return _flash_decode(
                q, k_cache, v_cache, valid_len, softcap=softcap, scale=scale,
                block_k=max(256, g["block_k"]),
                interpret=(impl == "pallas_interpret"))

        return _per_shard(kernel, q, k_cache, v_cache, valid_len,
                          n_heads=q.shape[1], n_kv_heads=k_cache.shape[1])
    return _ref.decode_reference(
        q, k_cache, v_cache, valid_len, softcap=softcap, scale=scale)


def ssd(
    x: jnp.ndarray,               # (B, L, H, P)
    dt: jnp.ndarray,              # (B, L, H)
    A: jnp.ndarray,               # (H,)
    Bm: jnp.ndarray,              # (B, L, G, N)
    Cm: jnp.ndarray,              # (B, L, G, N)
    *,
    chunk: int = 256,
    block_heads: int = 8,
    impl: Optional[str] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    impl = resolve_impl(impl)
    B, L, H, P = x.shape
    if impl in ("pallas", "pallas_interpret") and L % min(chunk, L) == 0 and Bm.shape[2] == 1:
        bh = block_heads
        while H % bh:
            bh //= 2
        return _ssd_kernel(x, dt, A, Bm, Cm, chunk=chunk, block_heads=max(bh, 1),
                           interpret=(impl == "pallas_interpret"))
    if impl == "naive":
        return _ref.ssd_reference(x, dt, A, Bm, Cm)
    ch = min(chunk, L)
    while L % ch:
        ch //= 2
    return _ref.ssd_chunked_reference(x, dt, A, Bm, Cm, chunk=max(ch, 1))


def ssd_decode(x_t, dt_t, A, B_t, C_t, state):
    return _ref.ssd_decode_reference(x_t, dt_t, A, B_t, C_t, state)
