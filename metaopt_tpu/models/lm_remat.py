"""What a rematerialised block of a decoder keeps besides its input: one
trade, time for memory, whose right side depends on size, so the rule reads
the sizes. It reads a pattern's specs through their answers alone
(``products``, ``kernel_keeps``, ``under_tp``: models/lm_layers.py) and names
no mechanism. ``rematerialised`` itself, which the 2017 blocks use too, is
models/transformer.py's.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from metaopt_tpu.models.transformer import held_parameters
from metaopt_tpu.ops.attention import REMAT_KEEPS
from metaopt_tpu.parallel.mesh import use_mesh

#: bytes of a trial's state a parameter: its value, AdamW's two moments and
#: its gradient, float32 each
STATE_BYTES_A_PARAMETER = 16


def remat_keeps(p, *, tokens: int = 0, d_model: int = 0, parameters: int = 0,
                bytes_limit: Optional[int] = None) -> Dict[str, Any]:
    """What a rematerialised block of the model keeps besides its input, as
    ``trial.setup``'s ``attrs["remat"]`` says it. ``keeps``: the attention
    kernels' names, those of the other kernels its mixers call and, for a
    model with a layer pattern ``p`` (None: without), as many of its layers'
    matrix products as fit the device: one device's ``tokens`` a step, its
    ``d_model`` (the other widths are the specs', as the device holds them:
    ``Pattern.under_tp``), its ``parameters`` and its memory's
    ``bytes_limit``. The candidates (:func:`products`) are taken in order of
    gain a byte: a product's FLOPs over the bytes of its output, which is
    its contracting width. The products of a name stand over all the layers
    that make it at once (``bytes``, each candidate name's) and a candidate
    is kept if it fits what is left of ``room``: half of what the limit
    leaves beside the state (``STATE_BYTES_A_PARAMETER``), the other half
    being the step's own (the blocks' inputs, one block's backward pass, the
    head's logits); one that does not fit is declined and the next is held
    against the same room. Without a limit (a backend that reports none, a
    model outside a trial) no product is kept. Every argument is explicit:
    the answer is made once, outside the traced function
    (models/lm.py::LMTrial)."""
    keeps = REMAT_KEEPS
    if p is None:
        return {"keeps": list(keeps)}
    for _, layers in p.by_kind():
        keeps += tuple(n for n in layers[0].mixer.kernel_keeps()
                       if n not in keeps)
    room = None if bytes_limit is None else max(
        0, bytes_limit - STATE_BYTES_A_PARAMETER * parameters) // 2
    candidates = products(p, tokens, d_model)
    left = room
    for _, sizes in sorted(candidates, key=lambda c: -c[0]):
        need = sum(sizes.values())
        if left is not None and need <= left:
            left -= need
            keeps += tuple(sizes)
    return {"keeps": list(keeps), "room": room,
            "bytes": {n: b for _, sizes in candidates
                      for n, b in sizes.items()}}


def products(p, tokens: int, d_model: int):
    """The matrix products a rematerialised block of the pattern can keep,
    as :func:`remat_keeps` takes them: (contracting width, {name: the bytes
    of its outputs over all the layers that make it}) a candidate, those of
    one width in the order they are tried: the feed-forwards', then the
    mixers' kind by kind (``Pattern.by_kind``). Each spec offers its own
    (``products``); candidates that share a name are one, kept or declined
    over all the layers that make it, at the mean of their widths (a model's
    feed-forwards of several widths; attention layers of which some make no
    k and v)."""
    specs = [layer.ffn for layer in p.layers] + [
        layer.mixer for _, layers in p.by_kind() for layer in layers]
    merged = []  # [the layers' widths, {name: bytes}] a candidate
    for spec in specs:
        for width, sizes in spec.products(d_model):
            same = next((c for c in merged if set(c[1]) & set(sizes)), None)
            if same is None:
                same = ([], {})
                merged.append(same)
            same[0].append(width)
            for name, a_token in sizes.items():
                same[1][name] = same[1].get(name, 0) + tokens * a_token
    return [(sum(widths) / len(widths), sizes) for widths, sizes in merged]


def param_init(model, batch_shape):
    """key -> ``model.init``'s parameters for rows of ``batch_shape``,
    jitted: ONE function, so that whoever asks for its shapes
    (``jax.eval_shape``: :func:`remat_on`'s count) and the sharded init
    that calls it share one trace of the model."""
    return jax.jit(lambda key: model.init(
        key, jnp.zeros(batch_shape, jnp.int32), train=False)["params"])


def remat_on(model, mesh: Mesh, batch_shape,
             init_params=None) -> Dict[str, Any]:
    """:func:`remat_keeps` of ``model`` (a models/lm.py::DecoderOnlyLM) for
    steps of ``batch_shape`` on ``mesh``: the share of the step one device
    sees, the parameters it holds (counted from the shapes of
    ``init_params``, the caller's :func:`param_init`, or of one made here),
    its share of every spec's widths and what its memory reports. Only a
    model with a layer pattern needs the count."""
    p = model.pattern
    if p is None:
        return remat_keeps(p)
    with use_mesh(mesh):  # as the init will trace it: the trace is shared
        shapes = jax.eval_shape(
            init_params or param_init(model, batch_shape),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    b, s = batch_shape
    across = lambda *axes: math.prod(  # noqa: E731
        mesh.shape.get(a, 1) for a in axes)
    return remat_keeps(
        p.under_tp(across("tp")), tokens=b * s // across("dp", "sp"),
        d_model=model.d_model, parameters=held_parameters(shapes, mesh),
        bytes_limit=device_bytes_limit(mesh))


def device_bytes_limit(mesh: Mesh) -> Optional[int]:
    """What a device of the mesh says its memory holds, None where the
    backend does not say (the CPU's)."""
    return (mesh.devices.flat[0].memory_stats() or {}).get("bytes_limit")
