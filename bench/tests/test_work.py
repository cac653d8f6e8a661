"""The benchmark's work counts against the program's perf model, which they
were copied from: they must agree on every suite shape."""
import pytest

from bench import work
from repro.core.perfmodel import gqa_suite, mha_suite, useful_flops

SHAPES = mha_suite() + gqa_suite()


@pytest.mark.parametrize("c", SHAPES, ids=[c.name for c in SHAPES])
def test_attention_flops_match_perfmodel(c):
    flops, nbytes = work.attention_prefill(c.batch, c.n_heads, c.n_kv_heads, c.seq_len,
                                           c.head_dim, causal=c.causal, window=c.window)
    assert flops == useful_flops(c)
    assert nbytes == 2 * c.batch * c.seq_len * c.head_dim * 2 * (c.n_heads + c.n_kv_heads)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 7), (False, 7), (False, None)])
def test_valid_pairs_counts_the_mask(causal, window):
    S = 23
    want = sum(1 for q in range(S) for k in range(S)
               if (not causal or k <= q) and (window is None or k > q - window))
    assert work.valid_pairs(S, causal, window) == want


def test_decode_work_counts_only_valid_slots():
    f, b = work.attention_decode(batch=2, n_heads=8, n_kv_heads=2, valid=100, head_dim=128)
    assert f == 4 * 2 * 8 * 128 * 100
    assert b == 2 * 2 * 128 * (2 * 2 * 100 + 2 * 8)


def test_model_counts_at_qwen2_widths():
    cfg = {"hidden_size": 3584, "num_attention_heads": 28, "num_key_value_heads": 4,
           "head_dim": 128, "intermediate_size": 18944, "vocab_size": 152064,
           "num_hidden_layers": 8, "mlp": "gated_silu"}
    per_layer = 3584 * 128 * (2 * 28 + 2 * 4) + 3 * 3584 * 18944
    assert work.layer_params(cfg) == per_layer
    assert work.weight_bytes(cfg) == 2 * (8 * per_layer + 3584 * 152064 + 3584)
    f = work.prefill_flops(cfg, 1, 4096)
    assert f == (2 * 4096 * per_layer * 8
                 + 8 * work.attention_prefill(1, 28, 4, 4096, 128)[0]
                 + 2 * 3584 * 152064)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.least_time(197e12, 1.0, peaks) == 1.0
    assert work.least_time(1.0, 819e9, peaks) == 1.0
