"""Attention block: projections + kernel dispatch + KV-cache management.

Cache layout per block position: (B, Hkv, Lc, Dh) with Lc = min(window,
max_len) — sliding-window layers keep a *ring buffer* of exactly the window,
which is what makes the long_500k cells tractable for SWA archs.  Keys are
rotary-encoded at write time (absolute positions), so ring order is free.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Block
from repro.distributed.context import batch_axes, div_axis, shard
from repro.kernels import ops
from repro.models.layers import norm_apply, norm_init, normal_init, rope_apply


def attn_init(key, cfg: ArchConfig, blk: Block, cross: bool = False):
    D, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    prefix = "c_" if cross else ""
    p = {
        prefix + "wq": normal_init(ks[0], (D, Hq * Dh)),
        prefix + "wk": normal_init(ks[1], (D, Hkv * Dh)),
        prefix + "wv": normal_init(ks[2], (D, Hkv * Dh)),
        prefix + "wo": normal_init(ks[3], (Hq * Dh, D)),
        prefix + "norm": norm_init(cfg, D),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((Hq * Dh,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * Dh,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * Dh,), jnp.float32)
    if cfg.post_norms and not cross:
        p["post_norm"] = norm_init(cfg, D)
    return p


def _project_qkv(h, p, cfg, compute_dtype, prefix=""):
    B, S, D = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = h @ p[prefix + "wq"].astype(compute_dtype)
    k = h @ p[prefix + "wk"].astype(compute_dtype)
    v = h @ p[prefix + "wv"].astype(compute_dtype)
    if cfg.qkv_bias and prefix == "":
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    return (q.reshape(B, S, Hq, Dh), k.reshape(B, S, Hkv, Dh), v.reshape(B, S, Hkv, Dh))


def attn_apply(
    x, p, cfg: ArchConfig, blk: Block, *,
    causal: bool, compute_dtype, pos_offset: int = 0,
    kv_source: Optional[jnp.ndarray] = None,      # cross-attention memory
    impl: Optional[str] = None, genome: Optional[dict] = None,
    return_kv: bool = False, use_rope: bool = True,
):
    """Full-sequence attention (train / prefill).  x: (B, S, D)."""
    with jax.named_scope("attn"):
        prefix = "c_" if kv_source is not None else ""
        with jax.named_scope("qkv"):
            h = norm_apply(x, p[prefix + "norm"], cfg).astype(compute_dtype)
            if kv_source is None:
                q, k, v = _project_qkv(h, p, cfg, compute_dtype)
                S_kv = x.shape[1]
            else:
                B, S, D = h.shape
                Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                q = (h @ p["c_wq"].astype(compute_dtype)).reshape(B, S, Hq, Dh)
                mem = kv_source.astype(compute_dtype)
                S_kv = mem.shape[1]
                k = (mem @ p["c_wk"].astype(compute_dtype)).reshape(B, S_kv, Hkv, Dh)
                v = (mem @ p["c_wv"].astype(compute_dtype)).reshape(B, S_kv, Hkv, Dh)

            if use_rope and kv_source is None:
                S = x.shape[1]
                qpos = jnp.arange(S) + pos_offset
                q = rope_apply(q, qpos, cfg.rope_theta)
                k = rope_apply(k, qpos, cfg.rope_theta)

        # (B, H, S, D) layout for the kernels.  The constraint keeps batch on the
        # DP axes AND heads on the model axis — a None batch dim here would FORCE
        # replication and make XLA all-gather the global batch at every layer
        # (the 16x activation-traffic bug found in the §Perf hillclimb).
        # When the head count does NOT divide the model axis (qwen2: 28 heads on
        # 16-way TP), fall back to SEQUENCE parallelism for Q/O: q-rows shard over
        # the model axis and attend to gathered (small, GQA) K/V — otherwise the
        # model axis sits idle and attention runs replicated (§Perf iter 2).
        with jax.named_scope("kernel"):
            ba = batch_axes() or None
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            head_ax = div_axis(cfg.n_heads)
            seq_ax = None
            if head_ax is None and kv_source is None:
                seq_ax = div_axis(qt.shape[2])          # model axis over q rows
            qt = shard(qt, ba, head_ax, seq_ax, None)
            kv_ax = div_axis(cfg.n_kv_heads)
            kt = shard(kt, ba, kv_ax, None, None)
            vt = shard(vt, ba, kv_ax, None, None)
            o = ops.attention(
                qt, kt, vt,
                causal=(causal and kv_source is None),
                window=blk.window if kv_source is None else None,
                softcap=cfg.attn_softcap, impl=impl, genome=genome)
            B, S = x.shape[0], x.shape[1]
            o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
        with jax.named_scope("out"):
            out = o @ p[prefix + "wo"].astype(compute_dtype)
            if cfg.post_norms and prefix == "":
                out = norm_apply(out.astype(x.dtype), p["post_norm"], cfg)
            result = x + out.astype(x.dtype)
    if return_kv:
        return result, (kt, vt)      # (B, Hkv, S, Dh) — pre-cache layout
    return result


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------


def cache_len(blk: Block, max_len: int) -> int:
    return min(blk.window, max_len) if blk.window else max_len


def attn_cache_init(cfg: ArchConfig, blk: Block, batch: int, max_len: int,
                    dtype=jnp.bfloat16):
    Lc = cache_len(blk, max_len)
    shape = (batch, cfg.n_kv_heads, Lc, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_from_prefill(kt, vt, blk: Block, max_len: int):
    """Arrange prefill K/V (B, Hkv, S, Dh) into the decode cache layout."""
    B, Hkv, S, Dh = kt.shape
    Lc = cache_len(blk, max_len)
    if S >= Lc:
        last_k, last_v = kt[:, :, S - Lc:], vt[:, :, S - Lc:]
        shift = (S - Lc) % Lc if blk.window else 0
        k = jnp.roll(last_k, shift, axis=2)
        v = jnp.roll(last_v, shift, axis=2)
    else:
        padw = ((0, 0), (0, 0), (0, Lc - S), (0, 0))
        k, v = jnp.pad(kt, padw), jnp.pad(vt, padw)
    return {"k": k, "v": v}


def attn_decode(
    x, p, cache, cfg: ArchConfig, blk: Block, *,
    pos, compute_dtype, cross_cache=None, enc_len: Optional[int] = None,
    impl: Optional[str] = None, genome: Optional[dict] = None, use_rope: bool = True,
):
    """Single-token attention.  x: (B, D); pos: scalar absolute position.
    Returns (x, the layer's cache with the new key and value written, the
    slot they went to)."""
    B, D = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            h = norm_apply(x, p["norm"], cfg).astype(compute_dtype)
            q = (h @ p["wq"].astype(compute_dtype))
            k = (h @ p["wk"].astype(compute_dtype))
            v = (h @ p["wv"].astype(compute_dtype))
            if cfg.qkv_bias:
                q, k, v = (q + p["bq"].astype(compute_dtype),
                           k + p["bk"].astype(compute_dtype),
                           v + p["bv"].astype(compute_dtype))
            q = q.reshape(B, Hq, Dh)
            k = k.reshape(B, Hkv, Dh)
            v = v.reshape(B, Hkv, Dh)
            if use_rope:
                q = rope_apply(q[:, None], pos, cfg.rope_theta)[:, 0]
                k = rope_apply(k[:, None], pos, cfg.rope_theta)[:, 0]

        Lc = cache["k"].shape[2]
        with jax.named_scope("kv_cache"):
            slot = (pos % Lc) if blk.window else pos
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k[:, :, None].astype(cache["k"].dtype), slot, axis=2)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v[:, :, None].astype(cache["v"].dtype), slot, axis=2)
        with jax.named_scope("kernel"):
            valid = jnp.minimum(pos + 1, Lc)
            valid_len = jnp.full((B,), valid, jnp.int32)
            o = ops.decode_attention(q, kc, vc, valid_len, softcap=cfg.attn_softcap,
                                     impl=impl, genome=genome)
        with jax.named_scope("out"):
            out = o.reshape(B, Hq * Dh) @ p["wo"].astype(compute_dtype)
            if cfg.post_norms:
                out = norm_apply(out.astype(x.dtype), p["post_norm"], cfg)
            x = x + out.astype(x.dtype)

        if cross_cache is not None:
            with jax.named_scope("qkv"):
                hc = norm_apply(x, p["c_norm"], cfg).astype(compute_dtype)
                qc = (hc @ p["c_wq"].astype(compute_dtype)).reshape(B, Hq, Dh)
            with jax.named_scope("kernel"):
                vl = jnp.full((B,), enc_len, jnp.int32)
                oc = ops.decode_attention(qc, cross_cache["k"].astype(compute_dtype),
                                          cross_cache["v"].astype(compute_dtype), vl,
                                          softcap=cfg.attn_softcap, impl=impl,
                                          genome=genome)
            with jax.named_scope("out"):
                x = x + (oc.reshape(B, Hq * Dh)
                         @ p["c_wo"].astype(compute_dtype)).astype(x.dtype)

    return x, {"k": kc, "v": vc}, slot
