"""Device milliseconds a step of the operations in direction ``backward``
(``trace.direction``: under ``transpose(`` and not a second run), every
layer: the gradients' products, the kernels' backward rules, the scatter
of the embedding's gradient, with AdamW's update where XLA fuses it into a
weight-gradient matmul.

``backward_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.direction_ms(records, "backward")
