"""Device milliseconds a step of the trunk between the layers, the scopes
``norm``, ``residual`` and ``loss`` together: the blocks' and the model's
norms outside a branch, the residual stream's sums and casts, and what the
loss function does around the model and ``readout_xent`` (the shifted rows,
the mask, the masked mean, the step's counts). Bandwidth-bound passes over
the residual stream, forward, second run and backward.

``trunk_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, layer_trace.TRUNK)
