"""Operations the algorithm needs, counted from shapes (never from XLA).

A multiply-add is two operations. Forward plus backward is three times
the forward's matrix work; recomputed operations do not count; embedding
gathers, normalisation, softmax and the optimizer are left out, as is
usual for a model-FLOPs utilisation.
"""

from __future__ import annotations

import json
import os


def peak(device_kind: str) -> dict:
    """The table row for ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise ValueError(f"no peak known for device_kind {device_kind!r}")
    return kinds[device_kind]


def encoder_layer_forward_flops(s: int, d: int, d_ff: int) -> int:
    """One token through one encoder layer at sequence length ``s``:
    q, k, v and output projections 8d^2, feed-forward 4 d d_ff, scores
    and weighted values 4 s d."""
    return 8 * d * d + 4 * d * d_ff + 4 * s * d


def decoder_layer_forward_flops(s: int, d: int, d_ff: int) -> int:
    """As the encoder layer, plus one cross-attention block."""
    return 16 * d * d + 4 * d * d_ff + 8 * s * d


def transformer_train_flops_per_item(s: int, d: int, layers: int, d_ff: int,
                                     vocab: int) -> float:
    """Per target token of an encoder-decoder with as many source tokens
    (copied from bench.py::transformer_train_flops, there per step)."""
    enc = layers * encoder_layer_forward_flops(s, d, d_ff)
    dec = layers * decoder_layer_forward_flops(s, d, d_ff)
    return 3.0 * (enc + dec + 2 * d * vocab)
