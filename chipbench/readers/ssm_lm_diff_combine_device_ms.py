"""Device milliseconds a step under the scope ``attention.diff``: the
differential combination of a layer's two maps: lambda from the four
64-vectors, a_1 - lambda a_2 in float32, the RMS norm over the pair's 128
and (1 - lambda_init), forward, second run and backward. A part of
``attention_proj_device_ms``, as ``attention.latent`` is in the
latent cell."""

from chipbench import program_trace


def read(records):
    return program_trace.scope_ms_a_step(records, "attention.diff", "train_step")
