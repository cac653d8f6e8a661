"""Pallas TPU single-token decode attention kernel (KV cache).

GQA packing: the ``rep = Hq // Hkv`` query heads that share one KV head are
processed together as the row dimension of the QK matmul, so the MXU sees a
(rep x D) @ (D x bk) GEMM instead of rep separate vector products — the TPU
analogue of the paper's GQA adaptation (§4.3, 30-minute transfer).

Grid: (B, Hkv, n_kv_blocks); the KV-block dimension is "arbitrary" and
carries the online-softmax stats in VMEM scratch.  ``valid_len`` is a
scalar-prefetch operand: it lands in SMEM before the grid runs, and each
step reads its row's length from there.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, _apply_softcap


def _decode_body(
    vl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, softcap, bk, nk, rep,
):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = vl_ref[b]
    # skip blocks entirely past the live region
    @pl.when(j * bk < valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                        # (rep, bk)
        s = _apply_softcap(s, softcap)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (rep, bk), 1)
        s = jnp.where(kpos < valid, s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_new = l_prev * alpha + p.sum(axis=-1)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nk - 1)
    def _epilogue():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "scale", "block_k", "interpret"))
def flash_decode(
    q: jnp.ndarray,               # (B, Hq, D)
    k_cache: jnp.ndarray,         # (B, Hkv, L, D)
    v_cache: jnp.ndarray,         # (B, Hkv, L, D)
    valid_len: jnp.ndarray,       # (B,) int32
    *,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    _, Hkv, L, _ = k_cache.shape
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    scale_ = scale if scale is not None else 1.0 / (D ** 0.5)

    bk = min(block_k, L)
    pad = (-L) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (L + pad) // bk

    q4 = q.reshape(B, Hkv, rep, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, D), lambda b, h, j, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, vl: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, vl: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda b, h, j, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, D), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_body, scale=scale_, softcap=softcap,
                          bk=bk, nk=nk, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(valid_len.astype(jnp.int32), q4, k_cache, v_cache)
    return out.reshape(B, Hq, D)
