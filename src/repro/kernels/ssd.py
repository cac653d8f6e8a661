"""Pallas TPU kernel for the Mamba-2 SSD (state-space duality) chunked scan.

The SSD algorithm splits the sequence into chunks of length Q: within a chunk
the output is an attention-like quadratic form (MXU-friendly); across chunks a
small (P x N) state is carried recurrently.  Grid: (B, n_head_blocks,
n_chunks) — the chunk dimension is "arbitrary" and carries the state in VMEM
scratch, exactly like the flash-attention accumulator.  The decay rates ``A``
sit in SMEM and are read as scalars per head.

This kernel inherits AVO's block-shape genome axes (chunk length, heads per
block) — the attention-specific axes are inapplicable to this attention-free
family (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_body(
    xt_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_out_ref, state_ref,
    *, Q, bh, nc,
):
    """One (batch, head block, chunk) step.  Every operand is a 2-D tile:
    x arrives transposed as (P, Q) per head and dt as a (1, Q) row, so the
    chunk's matmuls are plain NN/NT products and the per-position vectors
    are broadcast along lanes or sublanes — no in-kernel cumsum or 3-D
    contraction, which Mosaic cannot lower."""
    hb, c_idx = pl.program_id(1), pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    Bm = b_ref[0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)          # (Q, N)
    # ---- intra-chunk quadratic term (the "duality" GEMM), shared by heads ---
    cb = jax.lax.dot_general(                  # (Qi, Qj) = C @ B^T
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ii >= jj

    for h in range(bh):
        xt = xt_ref[0, h].astype(jnp.float32)  # (P, Q)
        dt = dt_ref[0, h].astype(jnp.float32)  # (1, Q)
        a = dt * a_ref[hb * bh + h]            # (1, Q) log-decay
        # inclusive cumsum as a masked row-sum: cum[i] = sum_{k<=i} a[k]
        cum_c = jnp.sum(jnp.where(causal, jnp.broadcast_to(a, (Q, Q)), 0.0),
                        axis=1, keepdims=True)                    # (Q, 1)
        cum_b = jnp.broadcast_to(cum_c, (Q, Q))                   # [i, j] = cum[i]
        cum_t = cum_b.T                                           # [i, j] = cum[j]
        cum_r = cum_t[0:1, :]                                     # (1, Q)
        total = cum_c[Q - 1:Q, :]                                 # (1, 1)

        # mask BEFORE exp — exp(seg)->inf on future entries NaN-poisons the VJP
        decay = jnp.exp(jnp.where(causal, cum_b - cum_t, -1e30))
        w = cb * decay * dt                                       # (Qi, Qj)
        y_intra = jax.lax.dot_general(                            # w @ x
            w, xt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                   # (Q, P)

        # ---- inter-chunk: carried state contribution ------------------------
        state = state_ref[h]                                      # (P, N)
        y_inter = jax.lax.dot_general(
            Cm, state, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.exp(cum_c)  # (Q, P)
        y_ref[0, h] = (y_intra + y_inter).astype(y_ref.dtype)

        # ---- state update ----------------------------------------------------
        w_state = jnp.exp(total - cum_r) * dt                     # (1, Q)
        upd = jax.lax.dot_general(
            xt * w_state, Bm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # (P, N)
        state_ref[h] = state * jnp.exp(total) + upd

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        st_out_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_heads", "interpret"))
def ssd_chunked(
    x: jnp.ndarray,               # (B, L, H, P)
    dt: jnp.ndarray,              # (B, L, H) — softplus'd step sizes
    A: jnp.ndarray,               # (H,) negative decay rates
    Bm: jnp.ndarray,              # (B, L, G=1, N)
    Cm: jnp.ndarray,              # (B, L, G=1, N)
    *,
    chunk: int = 256,
    block_heads: int = 8,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y: (B, L, H, P), final_state: (B, H, P, N))."""
    B, L, H, P = x.shape
    _, _, G, N = Bm.shape
    assert G == 1, "kernel handles G=1 (group broadcast done by caller)"
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    bh = min(block_heads, H)
    assert H % bh == 0, (H, bh)
    nc, nh = L // Q, H // bh

    # head-major layouts whose last two dims are (P, L), (1, L), (L, N) and
    # (L, P): every block then tiles cleanly on the TPU's (8, 128) layout
    xt = x.transpose(0, 2, 3, 1)                  # (B, H, P, L)
    dtt = dt.transpose(0, 2, 1)[:, :, None, :]    # (B, H, 1, L)
    y, st = pl.pallas_call(
        functools.partial(_ssd_body, Q=Q, bh=bh, nc=nc),
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, bh, P, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, bh, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, bh, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bm[:, :, 0], Cm[:, :, 0])
    return y.transpose(0, 2, 1, 3), st
