"""Model assembly: decoder-only LMs and encoder-decoder stacks for every
assigned architecture, built as a scan over repeating layer-pattern periods
(bounded HLO at any depth).

Public API:
  init_params(cfg, key)                          -> params pytree
  lm_logits(params, cfg, tokens, ...)            -> (B, S, V)
  lm_loss(params, cfg, batch, ...)               -> scalar
  prefill(params, cfg, tokens, max_len, ...)     -> (last_logits, cache)
  decode_step(params, cfg, cache, token, ...)    -> (logits, cache)

Every layer boundary of the model step carries a ``jax.named_scope``.  The
scopes are compile-time labels: they land in each op's metadata (the
``op_name`` of the HLO, ``tf_op`` in a profiler trace) and change nothing
that runs.  One fixed set of names, nested as shown:

  embed            token embedding and its scale (``_embed``)
  layers           the ``lax.scan`` over the layer stack; ops under it and in
                   no scope below are the loop's own moves: slicing weights
                   and, in decode, each layer's cache out of the stacked
                   cache the loop carries (copies the compiler adds around
                   the loop may carry no op name)
    attn           the attention sublayer (``attn_apply``, ``attn_decode``)
      qkv          norm, Q/K/V projections, biases, RoPE
      kernel       the attention kernel call with its layout transposes
      kv_cache     decode: the new key and value, written into the layer's
                   cache and, one row each, into the carried stack
      out          output projection, post-norm, residual
    mlp            ``mlp_apply``: norm, matmuls, activation, residual
    moe            ``moe_apply``
    ssm            ``mamba_apply``, ``mamba_decode``
  kv_cache         prefill: arranging K/V into the decode cache
  head             final norm, LM head, softcap (``_head``)

The Pallas kernels carry stable names of their own (``flash_attention``,
``flash_decode``, ``ssd``), which also name their custom calls in the HLO.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Block
from repro.distributed.context import batch_axes, div_axis, shard
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (logit_softcap, mlp_apply, mlp_init,
                                 norm_apply, norm_init, normal_init)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ArchConfig, blk: Block, causal_stack: bool):
    ks = jax.random.split(key, 4)
    p = {}
    if blk.kind == "attn":
        p["attn"] = attn_mod.attn_init(ks[0], cfg, blk)
        if blk.cross_attn and causal_stack:
            p["attn"].update(attn_mod.attn_init(ks[1], cfg, blk, cross=True))
    elif blk.kind == "mamba":
        p["mamba"] = ssm_mod.mamba_init(ks[0], cfg)
    if blk.mlp == "moe":
        p["moe"] = moe_mod.moe_init(ks[2], cfg)
    elif blk.mlp != "none":
        p["mlp"] = mlp_init(ks[3], cfg, blk)
    return p


def _stack_init(key, cfg: ArchConfig, n_periods: int, causal_stack: bool):
    """Per-pattern-position params stacked over periods (leading dim n_periods)."""
    def one_period(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return {f"pos{i}": _block_init(ks[i], cfg, blk, causal_stack)
                for i, blk in enumerate(cfg.pattern)}
    keys = jax.random.split(key, n_periods)
    per = [one_period(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


def init_params(cfg: ArchConfig, key) -> dict:
    ks = jax.random.split(key, 5)
    params = {
        "embed": normal_init(ks[0], (cfg.vocab_size, cfg.d_model)),
        "final_norm": norm_init(cfg, cfg.d_model),
        "dec": _stack_init(ks[1], cfg, cfg.n_periods, causal_stack=True),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(ks[2], (cfg.d_model, cfg.vocab_size))
    if cfg.enc_dec:
        assert cfg.n_enc_layers % len(cfg.pattern) == 0 or True
        # encoder uses a simplified uniform pattern: full attn + pattern[0].mlp
        params["enc"] = _stack_init(ks[3], cfg, cfg.n_enc_layers, causal_stack=False)
        params["enc_final_norm"] = norm_init(cfg, cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _apply_block(x, p, cfg: ArchConfig, blk: Block, *, causal, compute_dtype,
                 enc_out=None, impl=None, genome=None, collect=False):
    cache = {}
    if blk.kind == "attn":
        if collect:
            x, (kt, vt) = attn_mod.attn_apply(
                x, p["attn"], cfg, blk, causal=causal, compute_dtype=compute_dtype,
                impl=impl, genome=genome, return_kv=True)
            cache["kv"] = (kt, vt)
        else:
            x = attn_mod.attn_apply(
                x, p["attn"], cfg, blk, causal=causal, compute_dtype=compute_dtype,
                impl=impl, genome=genome)
        if blk.cross_attn and enc_out is not None:
            x = attn_mod.attn_apply(
                x, p["attn"], cfg, blk, causal=False, compute_dtype=compute_dtype,
                kv_source=enc_out, impl=impl, genome=genome)
    elif blk.kind == "mamba":
        x, mcache = ssm_mod.mamba_apply(x, p["mamba"], cfg, compute_dtype, impl=impl)
        if collect:
            cache["mamba"] = mcache
    if blk.mlp == "moe":
        x = moe_mod.moe_apply(x, p["moe"], cfg, compute_dtype)
    elif blk.mlp != "none":
        x = mlp_apply(x, p["mlp"], cfg, blk, compute_dtype)
    return x, cache


def _run_stack(params_stack, x, cfg: ArchConfig, pattern, *, causal, compute_dtype,
               enc_out=None, impl=None, genome=None, collect=False, remat=None):
    remat = cfg.remat if remat is None else remat

    # long patterns (jamba: 8 blocks/period) checkpoint per BLOCK inside the
    # per-period remat, bounding the backward live set to one block's
    # intermediates (measured 53 GiB/chip live on jamba train_4k without it)
    inner_ckpt = remat and not collect and len(pattern) > 2

    def period(x, pslice):
        caches = {}
        for i, blk in enumerate(pattern):
            x = shard(x, batch_axes() or None, None, None)
            apply_i = functools.partial(
                _apply_block, cfg=cfg, blk=blk, causal=causal,
                compute_dtype=compute_dtype, enc_out=enc_out,
                impl=impl, genome=genome, collect=collect)
            if inner_ckpt:
                apply_i = jax.checkpoint(apply_i)
            x, c = apply_i(x, pslice[f"pos{i}"])
            if collect:
                caches[f"pos{i}"] = c
        return x, (caches if collect else None)

    # NOTE (§Perf qwen2 iter4 / mixtral iter5, refuted): checkpointing with
    # dots_with_no_batch_dims_saveable cut recompute FLOPs (useful_frac
    # 0.78->0.93 on qwen2) but RAISED the dominant memory term ~10% (saved
    # GEMM outputs round-trip HBM) and inflated live temp bytes; full
    # per-period remat is the better point on this memory-bound Pareto.
    body = jax.checkpoint(period) if (remat and not collect) else period
    with jax.named_scope("layers"):
        x, caches = jax.lax.scan(body, x, params_stack)
    return x, caches


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def _embed(params, cfg: ArchConfig, tokens, prefix_embeds=None, compute_dtype=jnp.bfloat16):
    with jax.named_scope("embed"):
        x = params["embed"].astype(compute_dtype)[tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
        if prefix_embeds is not None and cfg.n_prefix_embeds:
            P = min(cfg.n_prefix_embeds, x.shape[1])
            x = jax.lax.dynamic_update_slice(
                x, prefix_embeds[:, :P].astype(compute_dtype), (0, 0, 0))
    return x


def _head(params, cfg: ArchConfig, x, compute_dtype, pad_vocab: bool = False):
    """LM head.  ``pad_vocab`` (training loss path) pads the vocab dim to a
    model-axis multiple so the fp32 logits chain TP-shards even for vocabs
    like 256206 that don't divide the axis — without it the whole logits
    chain replicates (measured ~22 GiB/chip live on seamless train_4k).
    Pad columns carry -1e30 logits, invisible to softmax; the padded shape is
    kept through the loss (slicing would force a re-replication)."""
    from repro.distributed.context import axis_size

    with jax.named_scope("head"):
        x = norm_apply(x, params["final_norm"], cfg).astype(compute_dtype)
        w = (params["embed"].astype(compute_dtype).T if cfg.tie_embeddings
             else params["lm_head"].astype(compute_dtype))
        V = cfg.vocab_size
        pad = 0
        if pad_vocab:
            mdl = axis_size("model")
            if mdl > 1 and V % mdl:
                pad = (-V) % mdl
                w = jnp.pad(w, ((0, 0), (0, pad)))
        logits = x @ w
        logits = logit_softcap(logits.astype(jnp.float32), cfg.logit_softcap)
        if pad:
            neg = jnp.full((pad,), -1e30, jnp.float32)
            logits = logits.at[..., V:].set(neg)
        return shard(logits, batch_axes() or None, *([None] * (logits.ndim - 2)),
                     div_axis(V + pad))


# ---------------------------------------------------------------------------
# full-sequence paths
# ---------------------------------------------------------------------------


def encode(params, cfg: ArchConfig, frames, *, compute_dtype=jnp.bfloat16,
           impl=None, genome=None):
    """Encoder stack over precomputed frame embeddings (audio stub)."""
    enc_pattern = (Block(kind="attn", mlp=cfg.pattern[0].mlp, cross_attn=False),)
    x = frames.astype(compute_dtype)
    x, _ = _run_stack(params["enc"], x, cfg, enc_pattern, causal=False,
                      compute_dtype=compute_dtype, impl=impl, genome=genome)
    return norm_apply(x, params["enc_final_norm"], cfg)


def lm_logits(params, cfg: ArchConfig, tokens, *, prefix_embeds=None,
              enc_frames=None, compute_dtype=jnp.bfloat16, impl=None,
              genome=None, pad_vocab: bool = False):
    x = _embed(params, cfg, tokens, prefix_embeds, compute_dtype)
    enc_out = None
    if cfg.enc_dec:
        assert enc_frames is not None, "enc-dec arch requires encoder frames"
        enc_out = encode(params, cfg, enc_frames, compute_dtype=compute_dtype,
                         impl=impl, genome=genome)
    x, _ = _run_stack(params["dec"], x, cfg, cfg.pattern, causal=True,
                      compute_dtype=compute_dtype, enc_out=enc_out,
                      impl=impl, genome=genome)
    return _head(params, cfg, x, compute_dtype, pad_vocab=pad_vocab)


def lm_loss(params, cfg: ArchConfig, batch, *, compute_dtype=jnp.bfloat16,
            impl=None, genome=None):
    """Next-token cross-entropy.  batch: {tokens, labels, [patch/frame embeds]}."""
    logits = lm_logits(
        params, cfg, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        enc_frames=batch.get("enc_frames"),
        compute_dtype=compute_dtype, impl=impl, genome=genome,
        pad_vocab=True)   # TP-shard the fp32 logits chain (pad cols = -inf)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------


def prefill(params, cfg: ArchConfig, tokens, max_len: int, *,
            prefix_embeds=None, enc_frames=None, cache_dtype=jnp.bfloat16,
            compute_dtype=jnp.bfloat16, impl=None, genome=None):
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, prefix_embeds, compute_dtype)
    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(params, cfg, enc_frames, compute_dtype=compute_dtype,
                         impl=impl, genome=genome)
    x, raw = _run_stack(params["dec"], x, cfg, cfg.pattern, causal=True,
                        compute_dtype=compute_dtype, enc_out=enc_out,
                        impl=impl, genome=genome, collect=True, remat=False)
    logits = _head(params, cfg, x[:, -1:], compute_dtype)[:, 0]

    cache = {"pos": jnp.asarray(S, jnp.int32), "layers": {}}
    for i, blk in enumerate(cfg.pattern):
        entry = {}
        c = raw[f"pos{i}"]
        if blk.kind == "attn":
            kt, vt = c["kv"]                      # (n_per, B, Hkv, S, Dh)
            with jax.named_scope("kv_cache"):
                arranged = jax.vmap(
                    lambda k, v: tuple(attn_mod.cache_from_prefill(k, v, blk, max_len).values()
                                       ))(kt.astype(cache_dtype), vt.astype(cache_dtype))
            entry["k"], entry["v"] = arranged
            if blk.cross_attn and cfg.enc_dec:
                entry["cross"] = _cross_cache(params["dec"], cfg, i, enc_out, compute_dtype)
        elif blk.kind == "mamba":
            entry["mamba"] = c["mamba"]
        cache["layers"][f"pos{i}"] = entry
    if cfg.enc_dec:
        cache["enc_len"] = enc_out.shape[1]
    return logits, cache


def _cross_cache(dec_stack, cfg, pos_i, enc_out, compute_dtype):
    """Project encoder memory through each period's cross-K/V (stacked)."""
    p = dec_stack[f"pos{pos_i}"]["attn"]
    B, Se, D = enc_out.shape
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

    def proj(wk, wv):
        k = (enc_out.astype(compute_dtype) @ wk.astype(compute_dtype))
        v = (enc_out.astype(compute_dtype) @ wv.astype(compute_dtype))
        return (k.reshape(B, Se, Hkv, Dh).transpose(0, 2, 1, 3),
                v.reshape(B, Se, Hkv, Dh).transpose(0, 2, 1, 3))

    k, v = jax.vmap(proj)(p["c_wk"], p["c_wv"])   # (n_per, B, Hkv, Se, Dh)
    return {"k": k, "v": v}


def init_decode_cache(cfg: ArchConfig, batch: int, max_len: int, *,
                      cache_dtype=jnp.bfloat16, enc_len: int = 0):
    """Zero cache for decode-only lowering (the decode_* dry-run cells)."""
    n_per = cfg.n_periods
    layers = {}
    for i, blk in enumerate(cfg.pattern):
        entry = {}
        if blk.kind == "attn":
            c = attn_mod.attn_cache_init(cfg, blk, batch, max_len, cache_dtype)
            entry["k"] = jnp.broadcast_to(c["k"], (n_per, *c["k"].shape))
            entry["v"] = jnp.broadcast_to(c["v"], (n_per, *c["v"].shape))
            if blk.cross_attn and cfg.enc_dec:
                shape = (n_per, batch, cfg.n_kv_heads, enc_len, cfg.head_dim)
                entry["cross"] = {"k": jnp.zeros(shape, cache_dtype),
                                  "v": jnp.zeros(shape, cache_dtype)}
        elif blk.kind == "mamba":
            c = ssm_mod.mamba_cache_init(cfg, batch)
            entry["mamba"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (n_per, *a.shape)), c)
        layers[f"pos{i}"] = entry
    cache = {"pos": jnp.asarray(max_len - 1, jnp.int32), "layers": layers}
    if cfg.enc_dec:
        cache["enc_len"] = enc_len
    return cache


def decode_step(params, cfg: ArchConfig, cache, token, *,
                compute_dtype=jnp.bfloat16, impl=None, genome=None):
    """One token for every sequence in the batch.  token: (B,) int32.

    The stacked cache rides the layer scan as its carry: each layer reads its
    own cache out of the stack and writes back only what changed (one key and
    value row; the whole small mamba state), so a donated cache is updated in
    place and never rebuilt."""
    x = _embed(params, cfg, token, compute_dtype=compute_dtype)
    pos = cache["pos"]
    enc_len = cache.get("enc_len", 0)

    def period(carry, xs):
        x, layers = carry
        pslice, li = xs
        layers = dict(layers)
        for i, blk in enumerate(cfg.pattern):
            p, stack = pslice[f"pos{i}"], dict(layers[f"pos{i}"])
            c = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, keepdims=False), stack)
            if blk.kind == "attn":
                x, kv, slot = attn_mod.attn_decode(
                    x, p["attn"], c, cfg, blk, pos=pos, compute_dtype=compute_dtype,
                    cross_cache=c.get("cross"), enc_len=enc_len,
                    impl=impl, genome=genome)
                # the rows are read back from the layer's updated cache, so
                # the write into the stack depends on the slice that read it:
                # the compiler orders the two and updates the stack in place
                with jax.named_scope("attn"), jax.named_scope("kv_cache"):
                    for n in ("k", "v"):
                        row = jax.lax.dynamic_slice_in_dim(kv[n], slot, 1, axis=2)
                        stack[n] = jax.lax.dynamic_update_slice(
                            stack[n], row[None], (li, 0, 0, slot, 0))
            elif blk.kind == "mamba":
                x, mc = ssm_mod.mamba_decode(x, p["mamba"], c["mamba"],
                                             cfg, compute_dtype)
                stack["mamba"] = jax.tree_util.tree_map(
                    lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, li, 0),
                    stack["mamba"], mc)
            if blk.mlp == "moe":
                x = moe_mod.moe_apply(x[:, None], p["moe"], cfg, compute_dtype)[:, 0]
            elif blk.mlp != "none":
                x = mlp_apply(x[:, None], p["mlp"], cfg, cfg.pattern[i], compute_dtype)[:, 0]
            layers[f"pos{i}"] = stack
        return (x, layers), None

    with jax.named_scope("layers"):
        (x, new_layers), _ = jax.lax.scan(
            period, (x, cache["layers"]), (params["dec"], jnp.arange(cfg.n_periods)))
    logits = _head(params, cfg, x, compute_dtype)
    new_cache = dict(cache, pos=pos + 1, layers=new_layers)
    return logits, new_cache
