"""``attention_proj_device_ms`` in a state-space decoder's cell, read by that
metric's own reader: the layer ``attention`` less ``attention.core``: the q, k, v and output projections with their biases, the scale and casts, and the differential combination (``ssm_lm_diff_combine_device_ms``) inside it.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("attention_proj_device_ms").read(records)
