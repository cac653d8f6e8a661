"""Shared layers: norms, rotary embeddings, MLP variants, initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Block


def normal_init(key, shape, scale=0.02, dtype=jnp.float32):
    return scale * jax.random.normal(key, shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_apply(x, w, cfg: ArchConfig, b=None):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        # gemma-style (1 + w) scaling when post_norms is on
        scale = (1.0 + w.astype(jnp.float32)) if cfg.post_norms else w.astype(jnp.float32)
        out = xf * scale
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps) * w.astype(jnp.float32)
        if b is not None:
            out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def norm_init(cfg: ArchConfig, shape_d: int):
    w = jnp.zeros((shape_d,), jnp.float32) if (cfg.norm == "rmsnorm" and cfg.post_norms) \
        else jnp.ones((shape_d,), jnp.float32)
    return w


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_apply(x, pos, theta: float):
    """x: (..., S, H, Dh) or (..., H, Dh) with matching pos (..., S) or scalar."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.asarray(pos, jnp.float32)
    ang = pos[..., None] * freqs                      # (..., S, half) or (half,)
    cos = jnp.cos(ang)[..., None, :]                  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ArchConfig, blk: Block):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"norm": norm_init(cfg, D)}
    if blk.mlp in ("gated_silu", "gated_gelu"):
        p["w_gate"] = normal_init(ks[0], (D, F))
        p["w_up"] = normal_init(ks[1], (D, F))
        p["w_down"] = normal_init(ks[2], (F, D))
    elif blk.mlp in ("squared_relu", "relu"):
        p["w_up"] = normal_init(ks[0], (D, F))
        p["w_down"] = normal_init(ks[1], (F, D))
    else:
        raise ValueError(blk.mlp)
    if cfg.post_norms:
        p["post_norm"] = norm_init(cfg, D)
    return p


def mlp_apply(x, p, cfg: ArchConfig, blk: Block, compute_dtype):
    with jax.named_scope("mlp"):
        h = norm_apply(x, p["norm"], cfg)
        h = h.astype(compute_dtype)
        if blk.mlp == "gated_silu":
            a = jax.nn.silu(h @ p["w_gate"].astype(compute_dtype))
            h = (a * (h @ p["w_up"].astype(compute_dtype))) @ p["w_down"].astype(compute_dtype)
        elif blk.mlp == "gated_gelu":
            a = jax.nn.gelu(h @ p["w_gate"].astype(compute_dtype), approximate=True)
            h = (a * (h @ p["w_up"].astype(compute_dtype))) @ p["w_down"].astype(compute_dtype)
        elif blk.mlp == "squared_relu":
            a = jax.nn.relu(h @ p["w_up"].astype(compute_dtype))
            h = (a * a) @ p["w_down"].astype(compute_dtype)
        elif blk.mlp == "relu":
            a = jax.nn.relu(h @ p["w_up"].astype(compute_dtype))
            h = a @ p["w_down"].astype(compute_dtype)
        if cfg.post_norms:
            h = norm_apply(h, p["post_norm"], cfg)
        return x + h.astype(x.dtype)


def logit_softcap(logits, cap: float):
    return cap * jnp.tanh(logits / cap) if cap else logits
