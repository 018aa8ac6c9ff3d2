"""Device milliseconds a step of the layer ``attention`` less its parts
that have a metric of their own (``attention.core``, ``attention.index``,
``attention.select``): the q, k, v and output projections forward, in a
rematerialised block's second run and backward, with AdamW's update where
XLA fuses it into a weight-gradient matmul; q/k norms, rotary positions,
the scaling and casts, and the 2017 model's masks."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["attention"],
                                 less=layer_trace.ATTENTION_PARTS)
