"""Device milliseconds a step of the LAYER ``ffn`` (``trace.layer_of``): the
gated SiLU feed-forward of every block, 2560 -> 10240 -> 2560
(``models/lm.py::GatedFeedForward``), forward, its second run under remat
and backward, with AdamW's update where XLA fuses it into a
weight-gradient matmul: 68 % of the cell's parameters."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["ffn"])
