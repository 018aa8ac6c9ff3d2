"""Device milliseconds a step of the LAYER ``ffn`` (``trace.layer_of``: the
outermost scope owns an operation): the leading dense layer's gated
feed-forward, 6144 wide, forward, second run and backward, with AdamW's
update where XLA fuses it into a weight-gradient matmul. The shared
experts run the same module under ``moe.shared`` and are ``moe``'s; a
union over the scope ``ffn`` would count them twice in the step's
partition, so this reads by layer, as ``ffn_device_ms`` does in the 2017
cell."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["ffn"])
