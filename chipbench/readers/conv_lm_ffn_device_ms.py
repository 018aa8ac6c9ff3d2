"""Device milliseconds a step of the LAYER ``ffn`` (``trace.layer_of``: the
outermost scope owns an operation): every dense feed-forward that is a
block's own, forward, its second run under remat and backward, with
AdamW's update where XLA fuses it into a weight-gradient matmul. The 2017
cell's two products and ReLU a layer; a pattern decoder's gated
feed-forward (``models/lm_layers.py::GatedFeedForward``) in every block of
the hybrid and the state-space cells and in the leading dense layer alone
of the latent and the gated cells: their shared experts run the same
module under ``moe.shared`` and are ``moe``'s, so a union over the SCOPE
``ffn`` would count them twice in the step's partition. The two 8k/16k
MoE decoders run no dense feed-forward and do not list this metric.

``ffn_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["ffn"])
