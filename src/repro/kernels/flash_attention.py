"""Genome-parameterized Pallas TPU flash-attention kernel.

This is the *search substrate* of the AVO reproduction: every structural
choice the paper's agent explored on Blackwell has a TPU-native analogue
expressed as a keyword argument, and ``core/search_space.KernelGenome``
enumerates exactly these axes.  The kernel is one implementation whose
behaviour is selected at trace time, so every genome materializes into a
concrete ``pl.pallas_call`` with explicit VMEM BlockSpec tiling.

Genome axes (paper analogue in brackets):
  block_q, block_k      [CTA tile shape / dual Q-stage — on TPU, the q-tile
                         granularity IS the stage structure, there being no
                         warp groups]
  rescale_mode          [§5.1 branchless accumulator rescaling: "branchless"
                         always multiplies by the correction factor (predicated
                         select of 1.0), "branched" wraps the rescale in
                         @pl.when — the TPU analogue of the divergent branch]
  mask_mode             [v8 bitmask causal masking: "block_skip" skips fully
                         masked K-blocks and bypasses mask application on fully
                         unmasked ones; "dense" always masks]
  div_mode              ["deferred" normalizes once in the epilogue (FA2-style,
                         lighter inner loop); "eager" keeps the accumulator
                         normalized every iteration (FA1-style)]
  kv_in_grid            [§5.2 pipeline overlap: True = K-loop as innermost
                         grid dimension, giving Mosaic's automatic
                         double-buffered DMA pipelining (overlapped);
                         False = in-kernel fori_loop over a VMEM-resident K/V
                         (serial; no cross-block DMA overlap).  NOTE: in the
                         False variant K/V is staged to VMEM in full, so the
                         true streaming-skip saving is modelled, not executed —
                         see core/perfmodel.py]

Correctness of every axis combination is asserted against ``ref.py`` in
``tests/test_flash_attention.py`` (interpret=True on CPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_STATS_LANES = 128  # TPU vector lane width for the (bq, 128) stats scratch
# Mosaic's default scoped-VMEM budget on v5e is 16 MiB, which refuses the
# kv_in_grid=False genomes (all of K/V staged) from s16384 up.  The chip has
# 128 MiB of VMEM per core; leave some to the compiler's internal scratch.
VMEM_LIMIT_BYTES = 100 * 2**20


def _precision(dtype):
    """f32 inputs get full-f32 matmuls (a TPU's default rounds them to
    bf16); narrower inputs keep the compiler's default."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _apply_softcap(s, softcap):
    return softcap * jnp.tanh(s / softcap) if softcap else s


def _mask_value(qpos, kpos, *, causal, window, k_limit):
    ok = kpos < k_limit
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return ok


def _block_classify(i, j, *, bq, bk, causal, window, k_limit, seq_mod=None):
    """(fully_masked, fully_unmasked) scalars for K-block j against Q-block i.

    Under GQA packing (seq_mod set) the q rows of a tile wrap around the true
    sequence, so a tile's q-position range is conservative: a tile that spans a
    wrap boundary covers [0, seq_mod) and is treated as never fully masked /
    never fully unmasked.
    """
    q_lo, q_hi = i * bq, i * bq + bq - 1
    if seq_mod is not None:
        wraps = (q_hi // seq_mod) != (q_lo // seq_mod)
        q_lo_m = jnp.where(wraps, 0, q_lo % seq_mod)
        q_hi_m = jnp.where(wraps, seq_mod - 1, q_hi % seq_mod)
        q_lo, q_hi = q_lo_m, q_hi_m
    k_lo, k_hi = j * bk, j * bk + bk - 1
    fully_masked = jnp.bool_(False)
    fully_unmasked = jnp.bool_(k_hi < k_limit)
    if causal:
        fully_masked |= k_lo > q_hi
        fully_unmasked &= k_hi <= q_lo
    if window is not None:
        fully_masked |= k_hi <= q_lo - window
        fully_unmasked &= k_lo > q_hi - window
    return fully_masked, fully_unmasked


def _fa_body_grid(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, softcap, bq, bk, nk, k_limit,
    rescale_mode, mask_mode, div_mode, seq_mod=None,
):
    adt = acc_ref.dtype            # f32, or bf16 under the acc_dtype genome
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    fully_masked, fully_unmasked = _block_classify(
        i, j, bq=bq, bk=bk, causal=causal, window=window, k_limit=k_limit,
        seq_mod=seq_mod)
    run = ~fully_masked if mask_mode == "block_skip" else jnp.bool_(True)

    prec = _precision(q_ref.dtype)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32,
        ) * scale                                      # (bq, bk)
        s = _apply_softcap(s, softcap)

        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        if seq_mod is not None:
            qpos = qpos % seq_mod
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask_value(qpos, kpos, causal=causal, window=window, k_limit=k_limit)
        if mask_mode == "block_skip":
            # bypass the mask entirely on interior (fully unmasked) blocks
            s = jnp.where(fully_unmasked, s, jnp.where(ok, s, NEG_INF))
        else:
            s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, 0]                           # (bq,)
        l_prev = l_ref[:, 0]
        m_blk = s.max(axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)                # (bq,) correction factor
        p = jnp.exp(s - m_new[:, None])                # (bq, bk)
        l_blk = p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)

        if div_mode == "deferred":
            l_new = l_prev * alpha + l_blk
            if rescale_mode == "branchless":
                acc_ref[...] = (acc_ref[...] * alpha[:, None] + pv).astype(adt)
            else:
                @pl.when(jnp.any(alpha < 1.0))
                def _rescale():
                    acc_ref[...] = (acc_ref[...] * alpha[:, None]).astype(adt)
                acc_ref[...] = (acc_ref[...] + pv).astype(adt)
        else:  # eager (FA1-style): accumulator kept normalized each step
            l_new = l_prev * alpha + l_blk
            l_safe = jnp.maximum(l_new, 1e-30)
            scale_prev = l_prev * alpha / l_safe
            if rescale_mode == "branchless":
                acc_ref[...] = (acc_ref[...] * scale_prev[:, None]
                                + pv / l_safe[:, None]).astype(adt)
            else:
                @pl.when(jnp.any(scale_prev < 1.0) | jnp.any(scale_prev > 1.0))
                def _rescale_e():
                    acc_ref[...] = (acc_ref[...] * scale_prev[:, None]).astype(adt)
                acc_ref[...] = (acc_ref[...] + pv / l_safe[:, None]).astype(adt)

        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        if div_mode == "deferred":
            acc = acc / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = acc.astype(o_ref.dtype)


def _fa_body_loop(
    q_ref, k_ref, v_ref, o_ref,
    *, scale, causal, window, softcap, bq, bk, nk, k_limit,
    rescale_mode, mask_mode, div_mode, seq_mod=None, acc_dtype="f32",
):
    adt = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16
    """K/V staged to VMEM in full; in-kernel fori_loop over K-blocks.

    With mask_mode="block_skip" the loop bounds themselves shrink for
    causal/windowed masks — the genuine "skip the block" path.
    """
    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    prec = _precision(q_ref.dtype)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[0, 0, rows, :].astype(jnp.float32)
        v = v_ref[0, 0, rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale
        s = _apply_softcap(s, softcap)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        if seq_mod is not None:
            qpos = qpos % seq_mod
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = _mask_value(qpos, kpos, causal=causal, window=window, k_limit=k_limit)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)
        acc = (acc.astype(jnp.float32) * alpha[:, None] + pv).astype(adt)
        l_new = l_prev * alpha + p.sum(axis=-1)
        return acc, m_new, l_new

    if mask_mode == "block_skip" and (causal or window is not None) and seq_mod is None:
        lo = jnp.int32(0)
        hi = jnp.int32(nk)
        if causal:
            hi = jnp.minimum(hi, (i * bq + bq + bk - 1) // bk)
        if window is not None:
            lo = jnp.maximum(lo, (i * bq - window + 1) // bk)
            lo = jnp.maximum(lo, 0)
    else:
        lo, hi = jnp.int32(0), jnp.int32(nk)

    acc0 = jnp.zeros((bq, q_ref.shape[-1]), adt)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc.astype(jnp.float32)
                   / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale", "block_q", "block_k",
        "rescale_mode", "mask_mode", "div_mode", "kv_in_grid", "gqa_pack",
        "acc_dtype", "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,               # (B, Hq, Sq, D)
    k: jnp.ndarray,               # (B, Hkv, Sk, D)
    v: jnp.ndarray,               # (B, Hkv, Sk, D)
    *,
    causal: bool = False,
    window: Optional[int] = None,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    rescale_mode: str = "branchless",
    mask_mode: str = "block_skip",
    div_mode: str = "deferred",
    kv_in_grid: bool = True,
    gqa_pack: bool = False,
    acc_dtype: str = "f32",       # "bf16" halves acc VMEM — and loses ~7
                                  # mantissa bits per accumulate: the scoring
                                  # function's correctness gate rejects it
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    rep = Hq // Hkv
    scale_ = scale if scale is not None else 1.0 / (D ** 0.5)

    seq_mod = None
    if gqa_pack and rep > 1:
        # pack the rep q-heads that share a KV head into one long q axis:
        # (B, Hkv*rep, Sq, D) -> (B, Hkv, rep*Sq, D).  K/V are then fetched
        # once per group instead of once per q head; causal/window masks use
        # the position modulo the true sequence length.
        q = q.reshape(B, Hkv, rep, Sq, D).reshape(B, Hkv, rep * Sq, D)
        Hq_orig, Sq_orig = Hq, Sq
        Hq, Sq = Hkv, rep * Sq
        rep = 1
        seq_mod = Sq_orig

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // bq
    nk = (Sk + pad_k) // bk

    kwargs = dict(
        scale=scale_, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk, k_limit=Sk,
        rescale_mode=rescale_mode, mask_mode=mask_mode, div_mode=div_mode,
        seq_mod=seq_mod,
    )
    out_shape = jax.ShapeDtypeStruct((B, Hq, Sq + pad_q, D), q.dtype)
    acc_jdtype = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    if kv_in_grid:
        grid = (B, Hq, nq, nk)
        o = pl.pallas_call(
            functools.partial(_fa_body_grid, **kwargs),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, D), acc_jdtype),
                pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
                pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(q, k, v)
    else:
        grid = (B, Hq, nq)
        Sk_pad = Sk + pad_k
        o = pl.pallas_call(
            functools.partial(_fa_body_loop, acc_dtype=acc_dtype, **kwargs),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sk_pad, D), lambda b, h, i, rep=rep: (b, h // rep, 0, 0)),
                pl.BlockSpec((1, 1, Sk_pad, D), lambda b, h, i, rep=rep: (b, h // rep, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(q, k, v)

    o = o[:, :, :Sq, :]
    if seq_mod is not None:
        o = o.reshape(B, Hq, Sq // seq_mod, seq_mod, D).reshape(
            B, Hq * (Sq // seq_mod), seq_mod, D)
    return o
