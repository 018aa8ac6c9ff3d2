"""Device milliseconds a step under the scope ``attention.latent``:
a latent layer's own part of attention's projections: the K/V
down-projection (2048 -> 512 + 64), the latent's norm, the up-projection
(512 -> 32 x 256) and the shared key's rotary, forward, in a
rematerialised block's second run (where ``remat_keeps`` declined the
up-projection's product it is made again from the kept latent) and
backward. A part of ``attention_proj_device_ms``, as
``moe.experts`` is of ``moe``."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.latent", "train_step")
