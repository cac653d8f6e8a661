"""Operations and bytes that the algorithm needs, computed from shapes.

These are the benchmark's own yardstick: they never come from the compiled
program or from the program's performance model, so a kernel's roofline
share reads the same work whatever implements it.  Bytes are HBM bytes at
the served dtype (bf16, 2 bytes).
"""
from __future__ import annotations

BYTES = 2


def valid_pairs(seq: int, causal: bool = True, window=None) -> int:
    """Query-key pairs that the mask keeps, for one head of one sequence
    (the FlashAttention convention: a causal row q sees keys 0..q)."""
    S = seq
    if causal and window:
        return sum(min(q + 1, window) for q in range(S))
    if causal:
        return S * (S + 1) // 2
    if window:
        return sum(min(q + 1, window) + (S - 1 - q) for q in range(S))
    return S * S


def attention_prefill(batch, n_heads, n_kv_heads, seq, head_dim, causal=True,
                      window=None):
    """(flops, bytes) of one attention call over a whole prompt:
    4 * D * Hq * valid pairs per sequence; q, k, v read and o written once."""
    flops = 4.0 * batch * n_heads * head_dim * valid_pairs(seq, causal, window)
    nbytes = BYTES * batch * seq * head_dim * (2 * n_heads + 2 * n_kv_heads)
    return flops, float(nbytes)


def attention_decode(batch, n_heads, n_kv_heads, valid, head_dim):
    """(flops, bytes) of one decode attention call: each query sees the
    ``valid`` cached keys; K and V up to ``valid`` plus q and o move.
    Slots past ``valid`` are not the algorithm's work."""
    flops = 4.0 * batch * n_heads * head_dim * valid
    nbytes = BYTES * batch * head_dim * (2 * n_kv_heads * valid + 2 * n_heads)
    return flops, float(nbytes)


def _dims(cfg):
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, hq, hkv, cfg.get("head_dim") or d // hq


def layer_params(cfg) -> int:
    """Matrix parameters of one layer: projections and MLP (biases and
    norms are vectors and left out)."""
    D, Hq, Hkv, Dh = _dims(cfg)
    n_mlp = 3 if cfg["mlp"] == "gated_silu" else 2
    return D * Dh * (2 * Hq + 2 * Hkv) + n_mlp * D * cfg["intermediate_size"]


def weight_bytes(cfg) -> float:
    """Bytes of every weight a decode step reads: the held layers, the
    final norm and the head (the embedding is read a row per token)."""
    D = cfg["hidden_size"]
    return float(BYTES * (cfg["num_hidden_layers"] * layer_params(cfg)
                          + D * cfg["vocab_size"] + D))


def prefill_flops(cfg, batch, seq) -> float:
    """Model FLOPs of one prefill: matmuls over every token, attention over
    the valid pairs, and the head on the last position of each sequence."""
    D, Hq, Hkv, Dh = _dims(cfg)
    L = cfg["num_hidden_layers"]
    matmul = 2.0 * batch * seq * layer_params(cfg) * L
    attn = attention_prefill(batch, Hq, Hkv, seq, Dh)[0] * L
    return matmul + attn + 2.0 * batch * D * cfg["vocab_size"]


def decode_flops(cfg, batch, valid) -> float:
    """Model FLOPs of one decode step with ``valid`` keys per sequence."""
    D, Hq, Hkv, Dh = _dims(cfg)
    L = cfg["num_hidden_layers"]
    return (2.0 * batch * layer_params(cfg) * L
            + attention_decode(batch, Hq, Hkv, valid, Dh)[0] * L
            + 2.0 * batch * D * cfg["vocab_size"])


def decode_bytes(cfg, batch, valid) -> float:
    """HBM bytes one decode step must move: all weights once, the valid
    K and V of every layer, and the new key and value written."""
    D, Hq, Hkv, Dh = _dims(cfg)
    L = cfg["num_hidden_layers"]
    kv = BYTES * 2 * batch * Hkv * Dh * (valid + 1) * L
    return weight_bytes(cfg) + kv


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
