"""Device milliseconds a step of the LAYER ``ffn`` (``trace.layer_of``: the
outermost scope owns an operation): the leading dense layer's gated feed-
forward, 8192 wide, forward, second run and backward, with AdamW's update
where XLA fuses it into a weight-gradient matmul. The shared expert runs the
same module under ``moe.shared`` and is ``moe``'s; a union over the scope
``ffn`` would count it twice in the step's partition, so this reads by
layer, as ``mla_lm_ffn_device_ms`` does."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["ffn"])
