"""The FLOP functions against counts made by hand."""

from chipbench import flops


def test_encoder_layer_by_hand():
    d, d_ff, s = 512, 2048, 256
    # q, k, v, out: 4 x 2 d^2; ffn: 2 x 2 d d_ff; scores and values:
    # 2 x 2 s d, all per token
    by_hand = 4 * 2 * d * d + 2 * 2 * d * d_ff + 2 * 2 * s * d
    assert flops.encoder_layer_forward_flops(s, d, d_ff) == by_hand


def test_transformer_matches_bench_py_per_step():
    b, s, d, layers, d_ff, vocab = 64, 256, 512, 6, 2048, 32000
    enc = layers * (8 * d * d + 4 * d * d_ff + 4 * s * d)
    dec = layers * (16 * d * d + 4 * d * d_ff + 8 * s * d)
    per_step = 3.0 * b * s * (enc + dec + 2 * d * vocab)  # bench.py's
    assert flops.transformer_train_flops_per_item(
        s, d, layers, d_ff, vocab) * b * s == per_step


def test_unknown_device_kind_is_an_error():
    import pytest

    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        flops.peak("cpu")
