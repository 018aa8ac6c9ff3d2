"""Device milliseconds a step of the layer ``embed``
(``trace.layer_of``): the embedding's gather and cast forward, and its
gradient, a scatter-add of a step's token rows (15.1 of 16.7 ms in the 8k
decoder's cell before this metric; PERF.md section 5), with AdamW's update
where XLA fuses it in.

``embed_device_ms`` under this name for ``lfm2-24b.steady-8k``: the
same body (an accepted entry's ``workloads`` list takes a new cell from a
``benchmark`` PR alone, which folds this copy back into it)."""

from chipbench import layer_trace


def read(records):
    return layer_trace.layers_ms(records, ["embed"])
