"""How the benchmark drives the program for a dense decoder-only transformer.

This is the only file of the benchmark that knows the program's names: it
turns a configuration file into the program's ``ArchConfig``, hands it the
benchmark's weights under the program's own tree (the same arrays, no copy),
and builds the two entry points that the window drives, exactly as a server
would: ``launch.serve.make_prefill`` and ``launch.serve.make_serve_step``,
jitted, bf16, Pallas kernels, the genome left to the program's default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Block
from repro.launch.serve import make_prefill, make_serve_step
from repro.models import init_decode_cache, init_params

IMPL = "pallas"          # the kernels the models serve with on a TPU


def arch_config(cfg: dict) -> ArchConfig:
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg.get("head_dim", 0), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], pattern=(Block(kind="attn", mlp=cfg["mlp"]),),
        qkv_bias=cfg["qkv_bias"], rope_theta=float(cfg["rope_theta"]),
        norm=cfg["norm"], norm_eps=cfg["norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], remat=False)


def program_params(w: dict, arch: ArchConfig) -> dict:
    """The benchmark's weights under the program's parameter tree, checked
    leaf by leaf against the shapes ``init_params`` declares."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in lw}
    attn["norm"] = lw["attn_norm"]
    mlp = {k: lw[k] for k in ("w_gate", "w_up", "w_down") if k in lw}
    mlp["norm"] = lw["mlp_norm"]
    params = {"embed": w["embed"], "final_norm": w["final_norm"],
              "lm_head": w["head"], "dec": {"pos0": {"attn": attn, "mlp": mlp}}}
    want = jax.eval_shape(functools.partial(init_params, arch), jax.random.key(0))
    got = jax.tree_util.tree_map(lambda x: x.shape, params)
    if jax.tree_util.tree_map(lambda x: x.shape, want) != got:
        raise ValueError(f"weights do not match the program's tree: {got}")
    return params


@functools.cache
def prefill_fn(arch: ArchConfig, max_len: int):
    """(params, tokens (B, S)) -> (last logits (B, V), greedy token (B,), cache)."""
    step = make_prefill(arch, max_len, jnp.bfloat16, impl=IMPL)

    def run(params, tokens):
        logits, cache = step(params, tokens)
        return logits, jnp.argmax(logits, -1).astype(jnp.int32), cache

    return jax.jit(run)


@functools.cache
def decode_fn(arch: ArchConfig):
    """(params, cache, token (B,)) -> (greedy token (B,), its logit (B,),
    cache); the cache is donated, so the step may update it in place."""
    step = make_serve_step(arch, jnp.bfloat16, impl=IMPL)

    def run(params, cache, token):
        logits, cache = step(params, cache, token)
        return jnp.argmax(logits, -1).astype(jnp.int32), jnp.max(logits, -1), cache

    return jax.jit(run, donate_argnums=(1,))


def decode_cache(arch: ArchConfig, kv, max_len: int, pos: int) -> dict:
    """The program's decode cache holding ``kv`` = (K, V), each
    (L, B, Hkv, max_len, Dh), with the next token going to slot ``pos``."""
    want = jax.eval_shape(functools.partial(
        init_decode_cache, arch, kv[0].shape[1], max_len))
    cache = {"pos": jnp.asarray(pos, jnp.int32),
             "layers": {"pos0": {"k": kv[0], "v": kv[1]}}}
    if jax.tree_util.tree_map(lambda x: x.shape, want) != \
            jax.tree_util.tree_map(lambda x: x.shape, cache):
        raise ValueError("the cache does not match the program's decode cache")
    return cache


def restart(cache: dict, pos: int) -> dict:
    return dict(cache, pos=jnp.asarray(pos, jnp.int32))
