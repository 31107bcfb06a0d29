"""Operation and byte counts against hand-computed values, and the
table of peaks."""
import pytest

from bench import counts

# hidden 4, 2 heads of 2, 1 kv head, MLP 8, one layer, vocabulary 10
TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "intermediate_size": 8, "num_hidden_layers": 1,
        "vocab_size": 10}
QWEN2_1_5B = {"hidden_size": 1536, "num_attention_heads": 12,
              "num_key_value_heads": 2, "head_dim": 128,
              "intermediate_size": 8960, "num_hidden_layers": 28,
              "vocab_size": 151936}


def test_layer_params():
    # q 16 + k,v 16 + q bias 4 + k,v bias 4 + o 16 + MLP 96 + norms 8
    assert counts.layer_params(TINY) == 160
    # qwen2-1.5b: 28 layers, tied embedding and final norm give the
    # published 1.54 B parameters
    total = (28 * counts.layer_params(QWEN2_1_5B) + 151936 * 1536 + 1536)
    assert counts.layer_params(QWEN2_1_5B) == 46_797_824
    assert total == 1_543_714_304


def test_prefill_and_decode_flops():
    # per token 2 * (16 + 16 + 16 + 96) = 288; per (q, k) pair
    # 2 * 2 * 2 heads * 2 = 16; head 2 * 4 * 10 = 80
    assert counts.prefill_flops(TINY, 1, 3) == 3 * 288 + 6 * 16 + 80
    assert counts.decode_flops(TINY, 2, 4) == 2 * (288 + 4 * 16 + 80)
    assert counts.generate_flops(TINY, 1, 3, 3) == (
        1040 + counts.decode_flops(TINY, 1, 4)
        + counts.decode_flops(TINY, 1, 5))


def test_decode_bytes():
    # weights 160 + embedding 40 + final norm 4, KV 2 x 4 positions x
    # (k, v) x 1 head x 2, all bf16
    assert counts.decode_bytes(TINY, 2, 4) == 2 * (204 + 32)
    # qwen2-1.5b at batch 8 over 2112 positions: 3.09 GB of weights and
    # 0.48 GB of KV
    b = counts.decode_bytes(QWEN2_1_5B, 8, 2112)
    assert b == 2 * (1_543_714_304 + 28 * 8 * 2112 * 2 * 2 * 128)


def test_probe_counts():
    assert counts.read_hbm_bytes(1024) == 1024 * 512 + 4
    assert counts.chase_hbm_loads(524287, 2) == 1048574


def test_peaks():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
