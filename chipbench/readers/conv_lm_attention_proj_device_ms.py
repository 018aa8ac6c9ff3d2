"""Device milliseconds a step of the layer ``attention`` less its parts
that have a metric of their own (``attention.core``, ``attention.index``,
``attention.select``): the q, k, v and output projections forward, in a
rematerialised block's second run and backward, with AdamW's update where
XLA fuses it into a weight-gradient matmul; q/k norms, rotary positions,
the scaling and casts, and the 2017 model's masks.

``attention_proj_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["attention"],
                                 less=layer_trace.ATTENTION_PARTS)
