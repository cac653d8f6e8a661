"""Mamba-2 block (SSD mixer) — prefill/train via the chunked SSD kernel,
decode via the O(1) recurrent update.

Layout follows the Mamba-2 reference: in_proj -> [z | x | B | C | dt],
depthwise causal conv over [x|B|C], SiLU, SSD, skip (D term), gated RMSNorm,
out_proj.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.layers import norm_apply, norm_init, normal_init


def _dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, H, conv_dim


def mamba_init(key, cfg: ArchConfig):
    s, d_in, H, conv_dim = _dims(cfg)
    D = cfg.d_model
    proj_out = 2 * d_in + 2 * s.n_groups * s.d_state + H
    ks = jax.random.split(key, 6)
    return {
        "norm": norm_init(cfg, D),
        "in_proj": normal_init(ks[0], (D, proj_out)),
        "conv_w": normal_init(ks[1], (s.conv_kernel, conv_dim), scale=0.1),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": normal_init(ks[2], (d_in, D)),
    }


def _split_proj(proj, cfg):
    s, d_in, H, _ = _dims(cfg)
    gn = s.n_groups * s.d_state
    z, xbc_dt = jnp.split(proj, [d_in], axis=-1)
    xv, Bv, Cv, dt = jnp.split(xbc_dt, [d_in, d_in + gn, d_in + 2 * gn], axis=-1)
    return z, xv, Bv, Cv, dt


def _gated_norm(y, z, w, eps):
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g * w.astype(jnp.float32)


def mamba_apply(x, p, cfg: ArchConfig, compute_dtype, impl=None):
    """Full-sequence path (train / prefill).  x: (B, S, D)."""
    with jax.named_scope("ssm"):
        s, d_in, H, conv_dim = _dims(cfg)
        B, S, D = x.shape
        h = norm_apply(x, p["norm"], cfg).astype(compute_dtype)
        proj = h @ p["in_proj"].astype(compute_dtype)
        z, xv, Bv, Cv, dt = _split_proj(proj, cfg)

        # depthwise causal conv over [x|B|C]
        xbc = jnp.concatenate([xv, Bv, Cv], axis=-1)                       # (B,S,conv_dim)
        K = s.conv_kernel
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * p["conv_w"][i].astype(compute_dtype) for i in range(K))
        conv = jax.nn.silu(conv + p["conv_b"].astype(compute_dtype))
        xv, Bv, Cv = jnp.split(conv, [d_in, d_in + s.n_groups * s.d_state], axis=-1)

        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])        # (B,S,H)
        A = -jnp.exp(p["A_log"])                                           # (H,)
        xh = xv.reshape(B, S, H, s.head_dim)
        Bm = Bv.reshape(B, S, s.n_groups, s.d_state)
        Cm = Cv.reshape(B, S, s.n_groups, s.d_state)
        y, state = ops.ssd(xh, dt, A, Bm, Cm, chunk=s.chunk, impl=impl)
        y = y + p["D_skip"].astype(y.dtype)[None, None, :, None] * xh
        y = y.reshape(B, S, d_in)
        y = _gated_norm(y, z, p["gate_norm"], cfg.norm_eps).astype(compute_dtype)
        out = y @ p["out_proj"].astype(compute_dtype)
        # decode-resumable cache pieces: final ssm state + conv tail
        conv_tail = xbc[:, -(K - 1):, :] if S >= K - 1 else jnp.pad(
            xbc, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return x + out.astype(x.dtype), {"ssm": state, "conv": conv_tail.astype(jnp.float32)}


def mamba_cache_init(cfg: ArchConfig, batch: int):
    s, d_in, H, conv_dim = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, H, s.head_dim, s.d_state), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_kernel - 1, conv_dim), jnp.float32),
    }


def mamba_decode(x, p, cache, cfg: ArchConfig, compute_dtype):
    """Single-token path.  x: (B, D); cache: {"ssm": (B,H,P,N), "conv": (B,K-1,C)}."""
    with jax.named_scope("ssm"):
        s, d_in, H, conv_dim = _dims(cfg)
        B, D = x.shape
        h = norm_apply(x, p["norm"], cfg).astype(compute_dtype)
        proj = h @ p["in_proj"].astype(compute_dtype)
        z, xv, Bv, Cv, dt = _split_proj(proj, cfg)

        xbc = jnp.concatenate([xv, Bv, Cv], axis=-1)                       # (B, conv_dim)
        K = s.conv_kernel
        hist = jnp.concatenate([cache["conv"].astype(compute_dtype), xbc[:, None]], axis=1)
        conv = jnp.einsum("bkc,kc->bc", hist, p["conv_w"].astype(compute_dtype))
        conv = jax.nn.silu(conv + p["conv_b"].astype(compute_dtype))
        xv, Bv, Cv = jnp.split(conv, [d_in, d_in + s.n_groups * s.d_state], axis=-1)

        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])        # (B,H)
        A = -jnp.exp(p["A_log"])
        xh = xv.reshape(B, H, s.head_dim)
        Bm = Bv.reshape(B, s.n_groups, s.d_state)
        Cm = Cv.reshape(B, s.n_groups, s.d_state)
        y, new_state = ops.ssd_decode(xh, dt, A, Bm, Cm, cache["ssm"])
        y = y + p["D_skip"].astype(y.dtype)[None, :, None] * xh
        y = y.reshape(B, d_in)
        y = _gated_norm(y, z, p["gate_norm"], cfg.norm_eps).astype(compute_dtype)
        out = y @ p["out_proj"].astype(compute_dtype)
        new_cache = {"ssm": new_state, "conv": hist[:, 1:].astype(jnp.float32)}
        return x + out.astype(x.dtype), new_cache
