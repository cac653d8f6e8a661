"""Plain float32 reference of a dense decoder-only transformer with GQA.

It follows the published layer equations (pre-norm, rotary position
embeddings in the rotate-half form, grouped-query attention, gated-SiLU or
squared-ReLU MLP, untied head) and knows nothing of the program under test:
it imports only JAX and NumPy, and it owns the layout of the weights, which
the benchmark makes from the seed.  Every matrix product runs in float32 at
``Precision.HIGHEST`` (a TPU rounds float32 operands to bfloat16 otherwise).

``control`` names the lower precision that stands in for the program when
the limits of ``correct`` are read (``CONTROLS``):

- ``"int8"``: every weight and every activation that enters a projection, the
  MLP or the head is rounded to int8 (per output channel for weights, per
  token for activations) before an exact float32 product, which is what an
  int8 serving path computes; attention stays in float32.
- ``"fp8_attn"``: only attention is lowered: q, k, v and the softmax
  probabilities are rounded to float8 (e4m3) before its two products, which
  is what an fp8 attention kernel computes; the rest stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
W_STD = 0.02        # matrices and embeddings, as the program's initializer
# wq and wk are scaled so that a query's scores over the keys have this
# standard deviation at any width (d_model * std**2): attention then picks a
# few keys out of thousands, as trained models do, and its output is as
# large as the MLP's.  At 0.02 the scores would spread by 1.4 at qwen2-7b's
# width, every query would average thousands of keys to almost nothing, and
# a wrong attention output would barely move the logits.
SCORE_STD = 3.0
NORM_STD = 0.1      # norm gains: 1 + N(0, 0.1), so a norm that drops its gain shows
BIAS_STD = 0.1      # QKV biases
Q_CHUNK = 256       # query rows per attention block
V_CHUNK = 16384     # vocabulary columns per head block
CONTROLS = ("int8", "fp8_attn")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return dict(D=d, Hq=hq, Hkv=hkv, Dh=cfg.get("head_dim") or d // hq,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def weight_shapes(cfg: dict) -> dict:
    """{name: shape} of every weight; per-layer leaves lead with the layer."""
    n = dims(cfg)
    D, Hq, Hkv, Dh, F, V, L = (n[k] for k in ("D", "Hq", "Hkv", "Dh", "F", "V", "L"))
    layers = {"attn_norm": (L, D), "wq": (L, D, Hq * Dh), "wk": (L, D, Hkv * Dh),
              "wv": (L, D, Hkv * Dh), "wo": (L, Hq * Dh, D), "mlp_norm": (L, D),
              "w_up": (L, D, F), "w_down": (L, F, D)}
    if cfg["qkv_bias"]:
        layers.update(bq=(L, Hq * Dh), bk=(L, Hkv * Dh), bv=(L, Hkv * Dh))
    if cfg["mlp"] == "gated_silu":
        layers["w_gate"] = (L, D, F)
    return {"embed": (V, D), "final_norm": (D,), "head": (D, V), "layers": layers}


def qk_std(cfg: dict) -> float:
    return float(np.sqrt(SCORE_STD / cfg["hidden_size"]))


def kv_std(cfg: dict) -> tuple[float, float]:
    """Standard deviations of a key and a value element as the projections
    of a normalized hidden state make them (the norm gain is about 1):
    what a decode cache made from the seed should hold."""
    d = cfg["hidden_size"]
    return float(np.sqrt(d) * qk_std(cfg)), float(np.sqrt(d) * W_STD)


def key_from_seed(seed: int) -> jax.Array:
    """A JAX key from any non-negative whole number (``--seed`` may pass 2**32)."""
    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))


def init_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every weight, made on the device from ``seed`` in one jitted call."""
    shapes = weight_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            name = path[-1].key
            if name.endswith("norm"):
                out.append((1.0 + NORM_STD * jax.random.normal(k, shape)).astype(dtype))
            elif name in ("bq", "bk", "bv"):
                out.append((BIAS_STD * jax.random.normal(k, shape)).astype(dtype))
            elif name in ("wq", "wk"):
                out.append((qk_std(cfg) * jax.random.normal(k, shape)).astype(dtype))
            else:
                out.append((W_STD * jax.random.normal(k, shape, dtype)).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_from_seed(seed))


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _f8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def linear(x, w, control=None):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if control == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def norm(x, g, cfg):
    g = g.astype(jnp.float32)
    if cfg["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"]) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"]) * g


def rope(x, pos, theta):
    """x: (B, H, T, Dh), pos: (T,).  Rotate-half form, as Qwen2/HF."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v, q_pos, k_pos, control=None):
    """Causal GQA attention by position.  q: (B, Hq, T, Dh); k, v:
    (B, Hkv, S, Dh); a key is seen where its position <= the query's.
    Query rows go in blocks of ``Q_CHUNK`` so the scores fit."""
    fp8 = control == "fp8_attn"
    if fp8:
        q, k, v = _f8(q), _f8(k), _f8(v)
    B, Hq, T, Dh = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    pad = (-T) % Q_CHUNK if T > Q_CHUNK else 0
    c = Q_CHUNK if T > Q_CHUNK else T
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pp = jnp.pad(q_pos, (0, pad), constant_values=-1)
    nq = (T + pad) // c
    qb = qp.reshape(B, Hkv, rep, nq, c, Dh).transpose(3, 0, 1, 2, 4, 5)
    pb = pp.reshape(nq, c)

    def block(args):
        qc, pc = args                                   # (B, Hkv, rep, c, Dh), (c,)
        s = jnp.einsum("bgrcd,bgsd->bgrcs", qc, k, precision=HI) / np.sqrt(Dh)
        s = jnp.where(k_pos[None, :] <= pc[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _f8(p)
        return jnp.einsum("bgrcs,bgsd->bgrcd", p, v, precision=HI)

    o = jax.lax.map(block, (qb, pb))                    # (nq, B, Hkv, rep, c, Dh)
    o = o.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, nq * c, Dh)
    return o[:, :, :T]


def _layer(x, lw, cfg, pos, prefix_kv, control):
    n = dims(cfg)
    B, T, _ = x.shape
    h = norm(x, lw["attn_norm"], cfg)
    q, k, v = (linear(h, lw[w], control) for w in ("wq", "wk", "wv"))
    if cfg["qkv_bias"]:
        q, k, v = q + lw["bq"].astype(jnp.float32), k + lw["bk"].astype(jnp.float32), \
            v + lw["bv"].astype(jnp.float32)
    q = q.reshape(B, T, n["Hq"], n["Dh"]).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, n["Hkv"], n["Dh"]).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, n["Hkv"], n["Dh"]).transpose(0, 2, 1, 3)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k_pos = pos
    if prefix_kv is not None:                           # keys at 0 .. P-1
        pk, pv = prefix_kv
        k = jnp.concatenate([pk.astype(jnp.float32), k], 2)
        v = jnp.concatenate([pv.astype(jnp.float32), v], 2)
        k_pos = jnp.concatenate([jnp.arange(pk.shape[2]), pos])
    o = attend(q, k, v, pos, k_pos, control)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    x = x + linear(o, lw["wo"], control)
    h = norm(x, lw["mlp_norm"], cfg)
    if cfg["mlp"] == "gated_silu":
        a = jax.nn.silu(linear(h, lw["w_gate"], control)) * linear(h, lw["w_up"], control)
    elif cfg["mlp"] == "squared_relu":
        a = jnp.square(jax.nn.relu(linear(h, lw["w_up"], control)))
    else:
        raise ValueError(f"unknown mlp {cfg['mlp']!r}")
    return x + linear(a, lw["w_down"], control)


def head(w, x, cfg, control):
    """Logits (..., V) in float32, the head taken in vocabulary blocks."""
    h = norm(x, w["final_norm"], cfg)
    V = w["head"].shape[1]
    return jnp.concatenate([linear(h, w["head"][:, i:i + V_CHUNK], control)
                            for i in range(0, V, V_CHUNK)], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control", "n_last"))
def _forward(w, tokens, start, prefix, *, cfg_items, control, n_last):
    cfg = dict(cfg_items)
    T = tokens.shape[1]
    pos = start + jnp.arange(T)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, xs):
        lw, pkv = xs
        return _layer(x, lw, cfg, pos, pkv, control), None

    x, _ = jax.lax.scan(body, x, (w["layers"], prefix))
    return head(w, x[:, T - n_last:], cfg, control)


def _freeze(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(w, cfg: dict, tokens, *, prefix=None, n_last: int = 1, control=None):
    """Float32 logits (B, n_last, V) of the last ``n_last`` positions of
    ``tokens`` (B, T).  With ``prefix`` = (K, V), each (L, B, Hkv, P, Dh) of
    rotary-encoded keys and values at positions 0 .. P-1, the tokens sit at
    positions P .. P+T-1 and attend to the prefix as well.  ``control``: one
    of ``CONTROLS``, or None for the reference itself."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    start = 0 if prefix is None else prefix[0].shape[3]
    return _forward(w, jnp.asarray(tokens, jnp.int32), jnp.int32(start), prefix,
                    cfg_items=_freeze(cfg), control=control, n_last=n_last)
