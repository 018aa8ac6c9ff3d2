"""``attention_proj_device_ms`` in a latent-attention MoE decoder's cell, read by
that metric's own reader: the layer ``attention`` less ``attention.core``: q's and the output projection, rotary on q, the scaling and casts, and the latent's part (``mla_latent_device_ms``) inside it.
An accepted metric's list of cells takes no new cell, so the cell reports it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("attention_proj_device_ms").read(records)
