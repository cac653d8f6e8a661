"""Serving: prefill + batched decode step factories and a request-batching
driver (continuous batching with in-flight slot reuse).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import decode_step, prefill


def make_serve_step(cfg: ArchConfig, compute_dtype=jnp.bfloat16,
                    impl: Optional[str] = None, genome: Optional[dict] = None):
    """One decode step for the whole batch; cache donated in the caller's jit."""

    def serve_step(params, cache, token):
        return decode_step(params, cfg, cache, token,
                           compute_dtype=compute_dtype, impl=impl, genome=genome)

    return serve_step


def make_prefill(cfg: ArchConfig, max_len: int, compute_dtype=jnp.bfloat16,
                 impl: Optional[str] = None, genome: Optional[dict] = None):
    """Prefill factory; the KV cache is kept in the compute dtype."""
    def prefill_step(params, tokens, **extras):
        return prefill(params, cfg, tokens, max_len,
                       compute_dtype=compute_dtype, cache_dtype=compute_dtype,
                       impl=impl, genome=genome, **extras)

    return prefill_step


# ---------------------------------------------------------------------------
# request-batching driver (example-scale; CPU-friendly)
# ---------------------------------------------------------------------------


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    output: list = field(default_factory=list)
    done: bool = False


class BatchedServer:
    """Static-batch server: groups pending requests to the batch size,
    prefills together (right-aligned pad), then decodes in lockstep."""

    def __init__(self, cfg: ArchConfig, params, batch_size: int = 4,
                 max_len: int = 256, compute_dtype=jnp.float32,
                 impl: Optional[str] = None):
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self._serve = jax.jit(make_serve_step(cfg, compute_dtype, impl=impl),
                              donate_argnums=(1,))
        self._prefill = jax.jit(make_prefill(cfg, max_len, compute_dtype,
                                             impl=impl))
        self._compute_dtype = compute_dtype

    def run(self, requests: list[Request]) -> list[Request]:
        for i in range(0, len(requests), self.batch_size):
            self._run_group(requests[i:i + self.batch_size])
        return requests

    def prefill(self, group: list[Request]):
        """Prefill a group's prompts as one batch, right-aligned to the
        longest: (last-position logits (B, V), decode cache)."""
        cfg = self.cfg
        B = len(group)
        plen = max(len(r.prompt) for r in group)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(group):
            toks[i, plen - len(r.prompt):] = r.prompt     # right-align
        extras = {}
        if cfg.enc_dec:
            extras["enc_frames"] = jnp.zeros((B, plen, cfg.d_model),
                                             self._compute_dtype)
        return self._prefill(self.params, jnp.asarray(toks), **extras)

    def _run_group(self, group: list[Request]) -> None:
        logits, cache = self.prefill(group)
        token = jnp.argmax(logits, -1).astype(jnp.int32)
        steps = max(r.max_new_tokens for r in group)
        for t in range(steps):
            for i, r in enumerate(group):
                if not r.done and len(r.output) < r.max_new_tokens:
                    r.output.append(int(token[i]))
                    r.done = len(r.output) >= r.max_new_tokens
            if all(r.done for r in group):
                break
            logits, cache = self._serve(self.params, cache, token)
            token = jnp.argmax(logits, -1).astype(jnp.int32)
