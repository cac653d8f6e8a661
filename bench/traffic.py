"""The one traffic generator.  A mix is a JSON file under ``bench/traffic/``;
this module reads its parameters and makes every input from the seed.

``kind: "prefill"`` -- closed loop, one batch of ``batch`` prompts of one
length at a time; lengths come in cycles that hold each of ``prompt_lens``
once, in an order drawn from the seed, so every seed sends the same work.

``kind: "decode"`` -- ``sessions`` sequences decode in lockstep, greedy,
against a cache of ``max_len`` slots whose first ``start`` slots hold keys
and values drawn from the seed (the cache a prompt of ``start`` tokens
would leave); when the cache is full every session restarts at ``start``.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
KINDS = ("prefill", "decode")


def load(name: str, root: Path = HERE) -> dict:
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name}: kind must be one of {KINDS}")
    return mix


def prefill_requests(mix: dict, seed: int, vocab: int):
    """Endless (cycle, prompt_len, tokens (batch, prompt_len) int32)."""
    rng = np.random.default_rng(seed)
    lens = list(mix["prompt_lens"])
    cycle = 0
    while True:
        for s in rng.permutation(lens):
            yield cycle, int(s), rng.integers(0, vocab, (mix["batch"], int(s)),
                                              dtype=np.int32)
        cycle += 1


def decode_tokens(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """Each session's first input token (sessions,) int32."""
    return np.random.default_rng(seed).integers(0, vocab, mix["sessions"],
                                                dtype=np.int32)


def kv_prefix(mix: dict, seed: int, n_layers: int, n_kv_heads: int,
              head_dim: int, std=(1.0, 1.0), sessions=None, length=None):
    """(K, V), each (n_layers, len(sessions), n_kv_heads, length, head_dim)
    bf16, made on the device in one jitted call: normal with the standard
    deviations ``std`` = (of K, of V) in the first ``start`` slots, zero after.  A session's values depend only on the seed,
    its layer and its index, so a subset of ``sessions`` regenerates the
    same rows.  ``length`` defaults to ``max_len``."""
    sessions = np.arange(mix["sessions"]) if sessions is None else np.asarray(sessions)
    length = mix["max_len"] if length is None else length
    start = mix["start"]
    key = jax.random.key(int(np.random.SeedSequence([seed, 1]).generate_state(1)[0]))

    def one(key, kind, layer, s):
        k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, kind), layer), s)
        x = jax.random.normal(k, (n_kv_heads, start, head_dim), jnp.float32)
        x = (x * jnp.asarray(std, jnp.float32)[kind]).astype(jnp.bfloat16)
        return jnp.pad(x, ((0, 0), (0, length - start), (0, 0)))

    # the key is an argument, not a constant, so one compiled program serves
    # every seed
    @jax.jit
    def build(key, sess):
        per = jax.vmap(one, (None, None, None, 0))
        # a layer at a time, so no more than one layer's temporaries live
        return jax.lax.map(lambda l: (per(key, 0, l, sess), per(key, 1, l, sess)),
                           jnp.arange(n_layers))

    return build(key, jnp.asarray(sessions, jnp.int32))
