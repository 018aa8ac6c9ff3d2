"""Device milliseconds a step of the layer ``ffn`` in the 2017 cell: the
two dense products of every layer's ``FeedForward`` and the ReLU, forward
and backward, with AdamW's update where XLA fuses it into a weight-gradient
matmul. (The hybrid decoder's cell reports its gated feed-forward as
``hybrid_lm_ffn_device_ms``; the MoE decoders run no dense one.)"""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["ffn"])
