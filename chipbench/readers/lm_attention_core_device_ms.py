"""``attention_core_device_ms`` in a pattern decoder's cell, read by that metric's own
reader: the Pallas kernels under a structural mask (``flash_fwd``, twice a
step with rematerialised blocks, ``flash_bwd``), the backward's delta and
the sum of a K/V head's gradients over its query heads.
An accepted metric's list of cells takes no new cell, so the cell reports
it under a name of its own."""

from chipbench.run import _reader


def read(records):
    return _reader("attention_core_device_ms").read(records)
