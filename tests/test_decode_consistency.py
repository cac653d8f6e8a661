"""Prefill + incremental decode must reproduce the full-sequence forward pass
(teacher forcing equivalence) for every architecture family — the strongest
integration test of the KV-cache / SSM-state serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import decode_step, init_params, lm_logits, prefill

ARCH_SUBSET = ["qwen2-7b", "gemma2-27b", "mamba2-780m", "jamba-v0.1-52b",
               "mixtral-8x22b", "h2o-danube-3-4b", "seamless-m4t-medium",
               "phi-3-vision-4.2b", "nemotron-4-15b", "moonshot-v1-16b-a3b"]


@pytest.mark.parametrize("name", ARCH_SUBSET)
def test_prefill_then_decode_matches_full_forward(name, tiny_archs):
    cfg = tiny_archs[name]
    B, S, T = 2, 12, 6                  # prefill 12 tokens, decode 6 more
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + T)), jnp.int32)
    extras = {}
    if cfg.modality == "vision" and cfg.n_prefix_embeds:
        extras["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_prefix_embeds, cfg.d_model)),
            jnp.float32)
    if cfg.enc_dec:
        extras["enc_frames"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32)

    # full forward (teacher forcing): logits for every position
    full = lm_logits(params, cfg, toks, compute_dtype=jnp.float32, **extras)

    # prefill on the first S tokens, then step one token at a time
    logits_p, cache = prefill(params, cfg, toks[:, :S], S + T,
                              compute_dtype=jnp.float32,
                              cache_dtype=jnp.float32, **extras)
    np.testing.assert_allclose(logits_p, full[:, S - 1], atol=2e-3, rtol=2e-3,
                               err_msg=f"{name}: prefill logits")
    for t in range(T - 1):
        logits_d, cache = decode_step(params, cfg, cache, toks[:, S + t],
                                      compute_dtype=jnp.float32)
        np.testing.assert_allclose(
            logits_d, full[:, S + t], atol=2e-3, rtol=2e-3,
            err_msg=f"{name}: decode step {t}")


def test_decode_cache_isolated_across_batch(tiny_archs):
    """Row 0's decode must not depend on row 1's tokens."""
    cfg = tiny_archs["qwen2-7b"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    b = a.at[1].set((a[1] + 5) % cfg.vocab_size)
    la, _ = prefill(params, cfg, a, 16, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    lb, _ = prefill(params, cfg, b, 16, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    np.testing.assert_allclose(la[0], lb[0], atol=1e-5)


@pytest.mark.parametrize("name", ["gemma2-27b", "h2o-danube-3-4b", "mixtral-8x22b"])
@pytest.mark.parametrize("S", [6, 20])
def test_windowed_ring_decodes_past_its_window(name, S, tiny_archs):
    """A sliding-window layer keeps a ring of its window's slots: decoding
    well past the window wraps the ring (more than once from the short
    prompt; from a prompt longer than the window, prefill's rolled ring),
    and each donated step writes its row at ``pos % window`` in place."""
    cfg = tiny_archs[name]
    window = max(b.window or 0 for b in cfg.pattern)
    B, T = 2, 2 * window + 4 - S
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(11).integers(0, cfg.vocab_size, (B, S + T)),
                       jnp.int32)
    full = lm_logits(params, cfg, toks, compute_dtype=jnp.float32)
    _, cache = prefill(params, cfg, toks[:, :S], S + T, compute_dtype=jnp.float32,
                       cache_dtype=jnp.float32)
    step = jax.jit(lambda c, t: decode_step(params, cfg, c, t, compute_dtype=jnp.float32),
                   donate_argnums=(0,))
    for t in range(T - 1):
        logits, cache = step(cache, toks[:, S + t])
        np.testing.assert_allclose(logits, full[:, S + t], atol=2e-3, rtol=2e-3,
                                   err_msg=f"{name}: position {S + t}")
    assert int(cache["pos"]) == S + T - 1 > 2 * window
