"""What the files of the pattern decoder's tests share (test_lm_pattern.py,
test_lm_pattern_experts.py, test_lm_pattern_remat.py, and the other
families' files that build SmallThinker's small description): the sizes,
the description, the seeded parameters, the reference expert layer, the
gradient leaves the comparisons name, a one-device mesh, a jaxpr's
equations, and the Pallas route interpreted here."""

import os

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

HI = jax.lax.Precision.HIGHEST


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


D, H, KV, HD, F, E, TOPK, V, S, WINDOW = 32, 4, 2, 16, 24, 16, 3, 64, 24, 8


def description(layers, held=(0, E), **over):
    sliding, rotary = zip(*layers)
    h = dict(hidden_size=D, num_attention_heads=H, num_key_value_heads=KV,
             head_dim=HD, num_hidden_layers=len(layers), vocab_size=V,
             sliding_window_layout=list(sliding), rope_layout=list(rotary),
             sliding_window_size=WINDOW, rope_theta=1.5e6,
             moe_num_primary_experts=E, moe_num_active_primary_experts=TOPK,
             moe_ffn_hidden_size=F, experts_held=held, rms_norm_eps=1e-6)
    h.update(over)
    return h


def ref_moe(m, logits, p, held, act=jax.nn.relu):
    """Held experts (``p``: theirs alone) applied to every token, weighed
    by the routing."""
    top, idx = jax.lax.top_k(logits, TOPK)
    w = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(m)
    first, count = held
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        hid = act(jnp.dot(m, p["gate"][e], precision=HI)) \
            * jnp.dot(m, p["up"][e], precision=HI)
        y = y + we[:, None] * jnp.dot(hid, p["down"][e], precision=HI)
    return y


def seeded(model, tokens, seed=0):
    """The model's parameters, unboxed, with a router spread enough that
    rounding rarely changes a token's chosen experts."""
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), tokens[:, :-1],
                   train=False)["params"])
    for name, sub in params.items():
        if "router" in sub:
            sub["router"]["kernel"] = 4.0 * sub["router"]["kernel"]
    params["embed"]["embedding"] = params["embed"]["embedding"].astype(
        jnp.float32)
    return params


KINDS = {"global-nope": (0, 0), "window-rope": (1, 1),
         "global-rope": (0, 1), "window-nope": (1, 0)}


LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale",
          "h0/norm_in/scale", "h0/norm_post/scale", "h0/router/kernel",
          "h0/attn/q/kernel", "h0/attn/k/kernel", "h0/attn/v/kernel",
          "h0/attn/out/kernel", "h0/experts/gate", "h0/experts/up",
          "h0/experts/down"]


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)


def _equations(jaxpr, kernels=True):
    """Every equation of a jaxpr and of the jaxprs inside its equations;
    without ``kernels`` a ``pallas_call``'s body (on-chip memory) is left
    out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if not kernels and eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, kernels)


def one_device():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))


def _on_the_kernels(patch):
    """The Pallas route as the chip takes it, interpreted here: the backend
    reads as the TPU, and the kernels' entry runs the interpreter on float32
    operands (inside the structural kernels' loops this CPU's dot takes no
    pair of bfloat16); the embedding's sorted gradient rule and the grouped
    layers' hand-over of q and k likewise."""
    import functools

    from metaopt_tpu.models import lm
    from metaopt_tpu.ops import attention, embed, grouped_hand_over

    real = attention.flash_attention

    def interpreted(q, k, v, mask=None, **kw):
        wide = [x.astype(jnp.float32) for x in (q, k, v)]
        return real(*wide, mask, interpret=True, **kw).astype(q.dtype)

    patch.setattr(jax, "default_backend", lambda: "tpu")
    patch.setattr(attention, "flash_attention", interpreted)
    patch.setattr(lm, "embed_rows", functools.partial(
        embed.embed_rows, interpret=True))
    patch.setattr(grouped_hand_over, "operand", functools.partial(
        grouped_hand_over.operand, interpret=True))
