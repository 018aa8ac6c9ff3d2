"""A pattern decoder's layers: each mixer and the gated feed-forward beside
the spec that describes it. models/moe.py holds the routed feed-forward
and its spec, models/lm_description.py the containers a description fills
(``Layer``, ``Pattern``); models/lm.py's one block reads a layer's entry.

A spec is a frozen dataclass of what a description says of one sublayer,
and the one place that answers for it:

- ``mix(block, x, *read)`` (a mixer) / ``before_mixer(block, n)`` and
  ``feed(block, m, read)`` (a feed-forward): the module it builds, under the
  name its parameters keep, called inside the block, whose ``d_model`` and
  ``eps`` it reads; a mixer returns (the branch, {name: what it could hand
  on to later layers}). ``counts()``: the shapes of what the module sows a
  step, by the trial's key.
- ``products(d_model)``: the matrix products a rematerialised block can
  keep of it, [(contracting width, {name: bytes a token})], products that
  read one input at one width one candidate; the names are ``KEPT``'s, the
  same dict the module marks its products from (``checkpoint_name``).
  ``kernel_keeps()``: the names its kernel's outputs carry (ops/).
- ``under_tp(tp)``: its share on a ``tp`` mesh axis.
- ``kind`` and ``describe(step, layers, sources)``: its line in
  ``trial.setup``'s ``attrs`` (``step``: models/lm_description.py::Step,
  what it is described for; ``layers``: the pattern's layers of the kind).
  ``attends``: the kinds that say a route and a mask are listed before the
  recurrences, in the span and among the rule's candidates.

What a block keeps where the rule finds the room (models/lm_remat.py) is
always a product as its matmul leaves it, BEFORE the norms, the
convolutions, rotary, the scale and the casts, which are made again from
it, elementwise: a norm's backward needs the product itself, so a kept
normed value would bring the matmul back.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from metaopt_tpu.models.transformer import _pinit
from metaopt_tpu.ops.attention import (REMAT_KEEPS, CausalMask, LatentKV,
                                       attend, attention_route)
from metaopt_tpu.parallel.sharding import with_mesh_partitioning
from metaopt_tpu.utils import trace


def _mask_said(route: str, window: Optional[int]) -> str:
    return ("structure" if route == "pallas" else "dense") + (
        f": causal, window {window}" if window else ": causal")


def _kernels_said(step, window: Optional[int]) -> dict:
    """Under ``kernels``, which causal kernels a layer under ``window``
    takes on the Pallas route over rows of ``step.seq_len`` and the keys
    they read a key seen (ops/window_attention.py::window_kernels, which
    asks the rule the call asks); {} on another route or without a window."""
    if step.route != "pallas" or not window or not step.seq_len:
        return {}
    from metaopt_tpu.ops.window_attention import window_kernels

    return {"kernels": window_kernels(window, step.seq_len)}


def _numbers(layers) -> list:
    return [layer.number for layer in layers]


class RMSNorm(nn.Module):
    """Under the scope ``norm``; inside a mixer (q/k norms, the gated norm
    of a linear layer) the mixer's scope is the outer one and owns the
    operations (utils/trace.py::layer_of)."""

    eps: float = 1e-6

    @nn.compact
    @trace.scope("norm")
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def _rms_norm(name: str, x, eps: float):
    return RMSNorm(eps, name=name)(x)


def _layer_norm(name: str, x, eps: float):
    """A float32 LayerNorm with weight and bias, under the scope ``norm``."""
    with trace.scope("norm"):
        return nn.LayerNorm(epsilon=eps, dtype=jnp.float32, name=name)(x)


#: a pattern's norm, kind and placement as one value: x + f(norm(x)) with
#: RMS norms, x + norm(f(x)) with RMS norms (the norms on the branches'
#: outputs), x + f(norm(x)) with LayerNorms of weight and bias. Each is
#: (the norm ``name, x, eps -> normed``, is it on the branch's input?).
NORMS = {"rms": (_rms_norm, True), "rms on the branches": (_rms_norm, False),
         "layer": (_layer_norm, True)}


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A layer's rotary rule as a value: the base ``theta``; how many of a
    head's channels turn (the FIRST ``turned``; None: all, a
    ``partial_rotary_factor`` of 1); YaRN's numbers, ``yarn`` = (factor,
    original positions, beta_fast, beta_slow), or None; and the ``factor``
    cos and sin are multiplied by (YaRN's ``attention_factor``; 1: as they
    are). ``Rotary(theta)`` is the plain rule."""

    theta: float
    turned: Optional[int] = None
    yarn: Optional[Tuple[float, int, float, float]] = None
    factor: float = 1.0

    def frequencies(self, d: int):
        """(d / 2,) float32: the angle a position turns pair j of the ``d``
        turned channels by. Plain: theta^(-2j/d). YaRN, as the transformers
        library's ``_compute_yarn_parameters`` has it with truncation on:
        pairs below ``low`` keep the plain frequency, pairs from ``high`` on
        take it over ``factor``, a straight ramp between, where ``low`` /
        ``high`` are the floor / ceiling of the pair that turns beta_fast /
        beta_slow times in the original positions."""
        plain = self.theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        if self.yarn is None:
            return plain
        factor, original, fast, slow = self.yarn
        pair = lambda turns: d * math.log(  # noqa: E731
            original / (turns * 2 * math.pi)) / (2 * math.log(self.theta))
        low = max(math.floor(pair(fast)), 0)
        high = min(math.ceil(pair(slow)), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        return plain * (1.0 - ramp) + plain / factor * ramp

    def said(self, d: int) -> str:
        """The rule in ``trial.setup``'s words, for heads ``d`` wide."""
        turned = f"{d if self.turned is None else self.turned} of {d}"
        if self.yarn is None:
            return f"plain {self.theta:g}, {turned}"
        return (f"yarn {self.theta:g} x{self.yarn[0]:g} over "
                f"{self.yarn[1]}, {turned}, cos and sin x {self.factor:.4f}")


def rope(x, rule, adjacent: bool = False):
    """Rotary positions 0..S-1 on ``x`` (B, S, H, D), float32, by ``rule``
    (a :class:`Rotary`; a bare base is the plain rule): pair j of the
    turned channels turns by pos * ``rule.frequencies``[j], the channels
    past ``rule.turned`` pass as they are. The two halves of the turned
    channels are the pairs, channels (j, j + turned/2) (the ``rotate_half``
    convention), or, with ``adjacent``, channels (2j, 2j + 1)
    (``rope_interleave``)."""
    if not isinstance(rule, Rotary):
        rule = Rotary(rule)
    s, d = x.shape[1], x.shape[-1]
    if rule.turned is not None and rule.turned < d:
        x = x.astype(jnp.float32)
        return jnp.concatenate(
            [rope(x[..., :rule.turned], dataclasses.replace(
                rule, turned=None), adjacent), x[..., rule.turned:]], axis=-1)
    freq = rule.frequencies(d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]  # (S, D/2)
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if rule.factor != 1.0:
        cos, sin = cos * rule.factor, sin * rule.factor
    x = x.astype(jnp.float32)
    if adjacent:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class Indexer(nn.Module):
    """Which keys each query attends to: ``n_heads`` index heads of width
    ``head_dim`` on one layer-normed key head, a weight a (query, head)
    read from the hidden state, and the ``top_k`` causal keys with the
    largest ``sum_j w[t, j] relu(q[t, j] . k[s])`` (ops/sparse_index.py).
    Float32 at matmul precision highest throughout: the choice hangs on the
    scores' last bits. It reads a ``stop_gradient`` and its choice is
    piecewise constant, so the next-token loss sends it no gradient: its
    parameters are a frozen part of the trial (models/lm.py::FROZEN)."""

    n_heads: int
    head_dim: int
    top_k: int
    rope_theta: Optional[float]

    @nn.compact
    def __call__(self, n):
        from metaopt_tpu.ops import sparse_index

        n = jax.lax.stop_gradient(n.astype(jnp.float32))
        with trace.scope("attention.index"):
            proj = lambda name, features: nn.DenseGeneral(  # noqa: E731
                features, use_bias=False, name=name,
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=with_mesh_partitioning(
                    nn.initializers.lecun_normal(),
                    (None,) * (1 + len(features))))(n)
            q = proj("q", (self.n_heads, self.head_dim))
            k = nn.LayerNorm(name="k_norm")(proj("k", (self.head_dim,)))
            if self.rope_theta is not None:
                q = rope(q, self.rope_theta)
                k = rope(k[:, :, None], self.rope_theta)[:, :, 0]
            w = proj("w", (self.n_heads,)) * (
                self.n_heads ** -0.5 * self.head_dim ** -0.5)
        mask, selected = sparse_index.select(q, k, w, self.top_k)
        b, s = n.shape[:2]
        self.sow("attn_stats", "selected_pairs", selected)
        self.sow("attn_stats", "causal_pairs",
                 jnp.asarray(b * s * (s + 1) // 2, jnp.int32))
        return mask


class _Spec:
    """What every spec answers alike unless it says otherwise: its share on
    a ``tp`` mesh axis is the fields ``TP`` names, each divided, and its
    module sows nothing."""

    TP: Tuple[str, ...] = ()

    def under_tp(self, tp: int):
        return dataclasses.replace(self, **{
            name: getattr(self, name) // tp for name in self.TP})

    def counts(self) -> Dict[str, Tuple[int, ...]]:
        return {}


class _Mixer(_Spec):
    """What every mixer spec answers alike unless it says otherwise."""

    attends = True

    def kernel_keeps(self) -> Tuple[str, ...]:
        return REMAT_KEEPS


#: the four projections' products of an attention layer, grouped or
#: differential; the last is the output projection's
_ATTENTION_KEPT = {"q": "attention.q_proj", "k": "attention.k_proj",
                   "v": "attention.v_proj", "out": "attention.out_proj"}


@dataclasses.dataclass(frozen=True)
class GroupedSpec(_Mixer):
    """Grouped attention as a description has it: the query and K/V heads
    held here and their width, a ``window`` or None, a rotary base
    ``theta`` or None (no positions), RMS norms of q and k (``qk_norm``:
    None, ``"head"``: over a head's width, ``"whole"``: over the projected
    width, the heads held here together under one scale vector),
    ``selection``: None, or (index heads, their width, top k) of an
    :class:`Indexer` whose keys the layer attends to; ``rotary``: the
    layer's rotary rule where it is not the plain one at ``theta`` over
    the whole head (:attr:`rule`), and ``gate``: None, or the activation (a
    key of ``GATES``) of a gate on attention's output, one number a head
    and token, read from the layer's input."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    theta: Optional[float]
    qk_norm: Optional[str]
    selection: Optional[Tuple[int, int, int]]
    rotary: Optional[Rotary] = None
    gate: Optional[str] = None

    #: the four projections' products and the gate's
    KEPT = {**_ATTENTION_KEPT, "gate": "attention.gate_proj"}
    TP = ("heads", "kv_heads")

    @property
    def rule(self) -> Optional[Rotary]:
        """The rotary rule, None without positions."""
        if self.theta is None:
            return None
        return self.rotary or Rotary(self.theta)

    def mix(self, block, x):
        return GroupedAttention(block.d_model, self, block.eps,
                                name="attn")(x), {}

    def counts(self):
        return {"selected_pairs": (2,), "causal_pairs": (2,)} \
            if self.selection else {}

    def products(self, d_model):
        kept, kv = self.KEPT, 2 * self.kv_heads * self.head_dim
        # the gate's product is float32, a number a head
        gate = {kept["gate"]: 4 * self.heads} if self.gate else {}
        return [(d_model, {kept["q"]: 2 * self.heads * self.head_dim,
                           kept["k"]: kv, kept["v"]: kv, **gate}),
                (self.heads * self.head_dim, {kept["out"]: 2 * d_model})]

    @property
    def kind(self) -> str:
        return ("selected" if self.selection else
                "window" if self.window else "global") + (
            "-nope" if self.theta is None else "-rope")

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.grouped_hand_over import hand_over

        q_and_k = {"hand_over": hand_over(
            step.route, step.mesh, self.qk_norm, self.theta is not None)}
        if not self.selection:
            said = {"route": step.route,
                    "mask": _mask_said(step.route, self.window),
                    **_kernels_said(step, self.window), **q_and_k}
            if self.rotary or self.gate:  # where a field says more than the kind
                said.update(
                    layers=_numbers(layers), heads=self.heads,
                    kv_heads=self.kv_heads,
                    rotary=self.rule.said(self.head_dim) if self.rule
                    else None,
                    gate=f"{self.gate} a head" if self.gate else None)
            return said
        from metaopt_tpu.ops.sparse_index import scores_of_a_row

        heads, width, top_k = self.selection
        said = {"route": step.route,
                "mask": f"selected: causal, top {top_k} of the index "
                        f"scores, {heads} index heads", **q_and_k}
        if step.seq_len:
            said["index_scores"] = scores_of_a_row(step.seq_len, width)
        return said


#: a gate's activation by the name a spec gives it
GATES = {"sigmoid": jax.nn.sigmoid}


class GroupedAttention(nn.Module):
    """Causal self attention with fewer K/V heads than query heads, no
    bias, as ``spec`` says (:class:`GroupedSpec`); the q/k norms' eps is
    ``eps``. With ``spec.gate``, head h's output is multiplied by
    act(x W_g)[h] before the output projection (a gate a head and token,
    the headwise form of arXiv:2505.06708; float32 at matmul precision
    highest, as a linear layer's gates are), under the scope
    ``attention.gate``. The projections' products carry the names of the
    spec's ``KEPT``: identities unless a block's policy asks for them.

    From q's and k's products to attention's operands (the q/k norms,
    rotary by the spec's rule, q over sqrt(head_dim), one rounding) is
    :func:`_handed_over`'s: one Pallas call an operand and direction where
    ops/grouped_hand_over.hand_over says so, else XLA's passes."""

    d_model: int
    spec: GroupedSpec
    eps: float

    @nn.compact
    @trace.scope("attention")
    def __call__(self, x):
        sp, kept = self.spec, self.spec.KEPT
        proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, sp.head_dim), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)))
        mask = CausalMask(sp.window)
        if sp.selection:
            mask = Indexer(*sp.selection, sp.theta, name="indexer")(x)
        gate_in, x = x, x.astype(jnp.bfloat16)
        q, k, v = (checkpoint_name(proj("q", sp.heads)(x), kept["q"]),
                   checkpoint_name(proj("k", sp.kv_heads)(x), kept["k"]),
                   checkpoint_name(proj("v", sp.kv_heads)(x), kept["v"]))
        out = attend(*_handed_over(self, q, k), v, mask)
        if sp.gate is not None:
            with trace.scope("attention.gate"):
                g = checkpoint_name(nn.DenseGeneral(
                    sp.heads, use_bias=False, name="gate",
                    precision=jax.lax.Precision.HIGHEST,
                    kernel_init=with_mesh_partitioning(
                        nn.initializers.lecun_normal(), (None, "tp")),
                )(gate_in.astype(jnp.float32)), kept["gate"])
                out = (out.astype(jnp.float32)
                       * GATES[sp.gate](g)[..., None]).astype(jnp.bfloat16)
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            use_bias=False, kernel_init=_pinit(True, ("tp", None, None)),
        )(out), kept["out"])


@dataclasses.dataclass(frozen=True)
class LatentSpec(_Mixer):
    """What a description says of its latent attention: the ``heads`` held
    here, the K/V latent's ``rank``, a head's q.k width without positions
    (``nope``) and with (``rope``: one rotary key for all heads, base
    ``theta``), its value width ``v``, and whether the rotary pairs are
    adjacent channels."""

    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    adjacent: bool
    theta: float

    #: q's product, the down-projection's (the latent and the shared rotary
    #: key before its rotation: 2 (rank + rope) bytes a token, the cheapest
    #: thing a block can keep), the up-projection's (2 heads (nope + v): the
    #: dearest, and made again from the kept latent by a norm and a
    #: rank-deep matmul) and the output projection's
    KEPT = {"q": "attention.q_proj", "latent": "attention.kv_latent",
            "up": "attention.kv_up", "out": "attention.out_proj"}
    TP = ("heads",)

    kind = "latent-rope"

    def mix(self, block, x):
        return LatentAttention(block.d_model, self, block.eps,
                               name="attn")(x), {}

    def products(self, d_model):
        # q and the down-projection are a candidate each: the second is a
        # tenth of the first and the last thing worth giving up
        kept = self.KEPT
        return [(d_model, {kept["q"]: 2 * self.heads * (self.nope
                                                        + self.rope)}),
                (d_model, {kept["latent"]: 2 * (self.rank + self.rope)}),
                (self.rank, {kept["up"]: 2 * self.heads * (self.nope
                                                           + self.v)}),
                (self.heads * self.v, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.latent_attention import hand_over

        return {"route": step.route, "mask": _mask_said(step.route, None),
                "layers": _numbers(layers), "heads": self.heads,
                "nope": self.nope, "rope": self.rope, "v": self.v,
                "rank": self.rank,
                "hand_over": hand_over(step.route, step.mesh, self.nope,
                                       self.v)}


def _pairs_first(w):
    """The last axis' channels (0, 1, 2, 3, ...) as (0, 2, ..., 1, 3, ...):
    rotary on the adjacent pairs of ``w`` is rotary on the halves of this,
    channel for channel, and a score sums over the channels in any order
    that q and the key share."""
    return jnp.swapaxes(w.reshape(*w.shape[:-1], -1, 2), -1, -2).reshape(
        w.shape)


class LatentAttention(nn.Module):
    """Causal self attention over a compressed K/V (the DeepSeek-V3
    family's): q of ``spec.heads`` heads ``nope + rope`` wide straight from
    x (no q rank); c, k_pe = split(x W_kva, [rank, rope]); k_nope, v =
    split(rmsnorm(c) W_kvb, [nope, v]) a head; rotary on q's last ``rope``
    columns and on k_pe, the ONE key all heads share; scores (q_nope .
    k_nope + q_pe . k_pe) (nope + rope)^-1/2; out ``v`` wide a head, then
    the output projection. No bias, no q/k norms. The down-projection, the
    latent's norm, the up-projection and the shared key's rotary are under
    the scope ``attention.latent``; the four matmuls' products carry the
    names of the spec's ``KEPT``.

    How the operands reach attention is ops/latent_attention.hand_over's
    to say. ``"copies"``: q, k, v (B, S, H, D), rotary and the scale in
    float32 arrays, a copy of the shared key joined to every head's
    k_nope: what the reference takes, and the tests' oracle. ``"in
    place"``: the matmuls leave their products feature-major and the
    kernels read them where they lie; q's product reaches them in one
    pass, with the rotary pairs' de-interleaving on W_q's and the shared
    key's rotary columns (the parameters keep the published order)."""

    d_model: int
    spec: LatentSpec
    eps: float

    @nn.compact
    @trace.scope("attention")
    def __call__(self, x):
        from metaopt_tpu.ops import latent_attention as la
        from metaopt_tpu.parallel.mesh import active_mesh

        sp, kept = self.spec, self.spec.KEPT
        mesh = active_mesh()
        in_place = la.hand_over(attention_route(0.0, mesh), mesh, sp.nope,
                                sp.v) == "in place"
        # in place the pairs are made halves where that costs a weight's
        # bytes (W_q's rotary columns) or the one key's, not q's
        halves = _pairs_first if in_place and sp.adjacent else (lambda w: w)
        adjacent = sp.adjacent and not in_place
        how = {"q": {}, "kv_b": {}, "out": {"axis": (-2, -1)}}
        if in_place:
            how = {"q": {"dot_general": lambda x, w, *a, **kw: la.project_t(
                       x, jnp.concatenate([w[..., :sp.nope],
                                           halves(w[..., sp.nope:])], -1),
                       *a, **kw)},
                   "kv_b": {"dot_general": la.project_t},
                   "out": {"axis": (1, 2), "dot_general": la.contract_t}}
        heads = lambda name, width: nn.DenseGeneral(  # noqa: E731
            (sp.heads, width), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)),
            **how[name])
        x = x.astype(jnp.bfloat16)
        q = checkpoint_name(heads("q", sp.nope + sp.rope)(x), kept["q"])
        with trace.scope("attention.latent"):
            down = checkpoint_name(nn.Dense(
                sp.rank + sp.rope, dtype=jnp.bfloat16, name="kv_a",
                use_bias=False, kernel_init=_pinit(True, (None, None)))(x),
                kept["latent"])
            c = RMSNorm(self.eps, name="kv_a_norm")(down[..., :sp.rank])
            kv = checkpoint_name(heads("kv_b", sp.nope + sp.v)(
                c.astype(jnp.bfloat16)), kept["up"])
            k_pe = rope(halves(down[..., None, sp.rank:]), sp.theta,
                        adjacent)[:, :, 0].astype(jnp.bfloat16)
        if in_place:  # q (B, H (nope + rope), S), kv (B, H (nope + v), S)
            q = la.rotary_scaled(q, sp.heads, sp.nope, sp.theta,
                                 1.0 / math.sqrt(sp.nope + sp.rope))
            out = attend(q, LatentKV(kv, k_pe.transpose(0, 2, 1), sp.nope),
                         None, CausalMask())
            out = out.reshape(out.shape[0], sp.heads, sp.v, -1)
        else:         # q (B, S, H, nope + rope), kv (B, S, H, nope + v)
            q_pe = rope(q[..., sp.nope:], sp.theta, adjacent)
            q = (jnp.concatenate(
                [q[..., :sp.nope].astype(jnp.float32), q_pe], axis=-1)
                / math.sqrt(sp.nope + sp.rope)).astype(jnp.bfloat16)
            k = jnp.concatenate([kv[..., :sp.nope], jnp.broadcast_to(
                k_pe[:, :, None], (*kv.shape[:3], sp.rope))], axis=-1)
            out = attend(q, k, kv[..., sp.nope:], CausalMask())
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, dtype=jnp.bfloat16, name="out", use_bias=False,
            kernel_init=_pinit(True, ("tp", None, None)), **how["out"],
        )(out), kept["out"])


@dataclasses.dataclass(frozen=True)
class GatedSpec(_Spec):
    """A gated feed-forward ``d_ff`` wide, its gate's ``activation`` by the
    name a published config gives it."""

    d_ff: int
    activation: str

    #: in order of gain a byte: the down product, the module's output (2
    #: d_model bytes a token; with the block's norm on the branch's output
    #: the backward pass needs it, and would run the matmul again for it),
    #: then the gate and the up product (2 d_ff each); ``act(gate) * up``
    #: is made again from those two, elementwise
    KEPT = {"down": "ffn.down", "gate": "ffn.gate", "up": "ffn.up"}
    TP = ("d_ff",)

    kind = "gated"

    def before_mixer(self, block, n):
        return None

    def feed(self, block, m, read):
        return GatedFeedForward(block.d_model, self.d_ff, self.activation,
                                name="mlp")(m)

    def products(self, d_model):
        kept = self.KEPT
        return [(self.d_ff, {kept["down"]: 2 * d_model}),
                (d_model, {kept["gate"]: 2 * self.d_ff,
                           kept["up"]: 2 * self.d_ff})]

    def describe(self, step, layers, depth):
        return {}


class GatedFeedForward(nn.Module):
    """(act(x W_gate) * (x W_up)) W_down, no bias; ``activation`` by name,
    as ``DroplessMoE`` takes it. The three products carry the names of
    ``GatedSpec.KEPT``: identities unless a block's policy asks for them.
    The gating is kept out of the matmuls around it (a barrier, below)."""

    d_model: int
    d_ff: int
    activation: str = "relu"

    @nn.compact
    @trace.scope("ffn")
    def __call__(self, x):
        dense = lambda name, n, axes: nn.Dense(  # noqa: E731
            n, dtype=jnp.bfloat16, name=name, use_bias=False,
            kernel_init=_pinit(True, axes))
        x = x.astype(jnp.bfloat16)
        act = {"relu": nn.relu, "silu": nn.silu}[self.activation]
        kept = GatedSpec.KEPT
        # The two products stand in memory before the gating reads them
        # and, by the barrier's transpose, so do their gradients before the
        # four matmuls that read those. Left to itself XLA makes
        # act(gate) * up and its derivative inside those matmuls' operands,
        # again for every pass over a tile: on a v5e they then take 7.7-9.5
        # ms where a matmul on operands that stand takes 3.9 (PERF.md
        # section 6, PR 33).
        gate, up = jax.lax.optimization_barrier((
            checkpoint_name(dense("gate", self.d_ff, (None, "tp"))(x),
                            kept["gate"]),
            checkpoint_name(dense("up", self.d_ff, (None, "tp"))(x),
                            kept["up"])))
        return checkpoint_name(
            dense("down", self.d_model, ("tp", None))(act(gate) * up),
            kept["down"])


@dataclasses.dataclass(frozen=True)
class LinearSpec(_Mixer):
    """What a description says of its linear-attention layers; ``describe``
    says under ``"hand_over"`` what :func:`_delta_form`, below, answers."""

    heads: int              # held here
    of: int                 # the layer's published count
    key_dim: int
    value_dim: int
    conv: int               # taps of the short convolutions
    neg_eigval: bool        # beta in (0, 2), not (0, 1)

    #: the six input projections' products (the two gates' float32: nothing
    #: in bytes, six passes at precision highest in time) and the output
    #: projection's
    KEPT = {n: f"linear_attention.{n}_proj"
            for n in ("q", "k", "v", "g", "a", "b", "out")}
    TP = ("heads",)

    attends = False
    kind = "linear"

    def mix(self, block, x):
        return LinearAttention(block.d_model, self, block.eps,
                               name="linear")(x), {}

    def kernel_keeps(self):
        from metaopt_tpu.ops import linear_attention

        return linear_attention.REMAT_KEEPS

    def products(self, d_model):
        kept = self.KEPT
        keys, values = 2 * self.heads * self.key_dim, \
            2 * self.heads * self.value_dim
        return [(d_model, {kept["q"]: keys, kept["k"]: keys,
                           kept["v"]: values, kept["g"]: values,
                           kept["a"]: 4 * self.heads,
                           kept["b"]: 4 * self.heads}),
                (self.heads * self.value_dim, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.linear_attention import linear_attention_route

        route = linear_attention_route()
        return {**route, "layers": _numbers(layers),
                "hand_over": _delta_form(self, route["route"], step.mesh),
                "heads": [self.heads, self.of], "key_dim": self.key_dim,
                "value_dim": self.value_dim, "conv": self.conv}


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` as Gated DeltaNet's published initialiser draws it:
    log of U(0, 16) (from 2**-6 on, so that the log is finite)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 2.0 ** -6, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` likewise: softplus^-1 of dt, log dt ~ U(log 1e-3, log
    1e-1): with ``A_log``, a decay of exp(-A dt) a token at a zero input."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def short_conv(x, taps):
    """Causal depthwise convolution along axis 1 of ``x`` (B, T, ...) with
    ``taps`` (K, ...), no bias: y_t = sum_i taps[i] x_{t - (K - 1) + i},
    x before the row's start = 0."""
    k, t = taps.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (k - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(taps[i] * x[:, i:i + t] for i in range(k))


class LinearAttention(nn.Module):
    """A gated-delta-rule mixer (Gated DeltaNet, arXiv:2412.06464) over the
    ``spec.heads`` heads held here: q, k (width ``key_dim``) and v (width
    ``value_dim``) each projected, passed through a causal depthwise
    convolution of ``conv`` taps and a SiLU; q and k L2-normalised over a
    head (q times key_dim^-1/2); a step beta = sigmoid(x W_b) (twice that
    with ``neg_eigval``) and a log decay g = -exp(A_log) softplus(x W_a +
    dt_bias) a (token, head); the recurrence (ops/linear_attention.py, the
    one rule there names its route; no positions); an RMS norm over a
    head's ``value_dim`` gated by silu(x W_g); the output projection.
    Element-wise work, gates and norms in float32; the two gates'
    projections float32 at matmul precision highest, as a router's are. The
    seven projections' products carry the names of the spec's ``KEPT``
    (identities unless a block's policy asks for them). Between the q, k,
    v products and the rule, and between the rule's output and ``out``,
    the form is ops/delta_hand_over.hand_over's to say (:func:`_delta_mixed`
    below asks it): ``"one pass"``, a Pallas call a side and direction
    around the scan's heads-first door, or ``"passes"``, XLA's."""

    d_model: int
    spec: LinearSpec
    eps: float

    @nn.compact
    @trace.scope("linear_attention")
    def __call__(self, x):
        sp, kept = self.spec, self.spec.KEPT
        proj = lambda name, width: nn.DenseGeneral(  # noqa: E731
            (sp.heads, width), axis=-1, dtype=jnp.bfloat16, name=name,
            use_bias=False, kernel_init=_pinit(True, (None, "tp", None)))
        gate = lambda name: nn.DenseGeneral(  # noqa: E731
            sp.heads, use_bias=False, name=name,
            precision=jax.lax.Precision.HIGHEST,
            kernel_init=with_mesh_partitioning(
                nn.initializers.lecun_normal(), (None, "tp")))
        own = lambda name, init, shape, axes: self.param(  # noqa: E731
            name, with_mesh_partitioning(init, axes), shape)
        taps = lambda name, width: own(  # noqa: E731  U(-1/2, 1/2) at 4 taps
            name, nn.initializers.variance_scaling(
                1 / 3, "fan_in", "uniform", in_axis=0, out_axis=(1, 2)),
            (sp.conv, sp.heads, width), (None, "tp", None))
        xb, xf = x.astype(jnp.bfloat16), x.astype(jnp.float32)
        # (a projection's product, its convolution's taps) by name
        mixed = {name: (checkpoint_name(proj(name, width)(xb), kept[name]),
                        taps("conv_" + name, width))
                 for name, width in (("q", sp.key_dim), ("k", sp.key_dim),
                                     ("v", sp.value_dim))}
        beta = jax.nn.sigmoid(checkpoint_name(gate("b")(xf), kept["b"])) \
            * (2.0 if sp.neg_eigval else 1.0)
        g = -jnp.exp(own("A_log", _decay_init, (sp.heads,), ("tp",))) \
            * jax.nn.softplus(checkpoint_name(gate("a")(xf), kept["a"]) + own(
                "dt_bias", _dt_bias_init, (sp.heads,), ("tp",)))
        y = _delta_mixed(self, mixed, g, beta, checkpoint_name(
            proj("g", sp.value_dim)(xb), kept["g"]))
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            use_bias=False, kernel_init=_pinit(True, ("tp", None, None)),
        )(y), kept["out"])


@dataclasses.dataclass(frozen=True)
class StateSpaceSpec(_Mixer):
    """A Mamba-1 mixer's sizes: ``d_inner`` channels of ``d_state`` states,
    a convolution of ``d_conv`` taps, steps of rank ``dt_rank``."""

    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int

    #: the input projection's product (x and z before the convolution and
    #: the gate), x_proj's (delta, B, C before the step's projection) and
    #: dt_proj's (before the softplus), and the output projection's
    KEPT = {"in": "ssm.in_proj", "x": "ssm.x_proj", "dt": "ssm.dt_proj",
            "out": "ssm.out_proj"}
    TP = ("d_inner",)

    attends = False
    kind = "ssm"

    def mix(self, block, x):
        out, y = StateSpaceMixer(block.d_model, self, name="ssm")(x)
        return out, {"memory": y}

    def kernel_keeps(self):
        from metaopt_tpu.ops import selective_scan

        return selective_scan.REMAT_KEEPS

    def products(self, d_model):
        kept = self.KEPT
        return [(d_model, {kept["in"]: 2 * 2 * self.d_inner}),
                (self.d_inner, {kept["x"]: 4 * (self.dt_rank
                                                + 2 * self.d_state)}),
                (self.dt_rank, {kept["dt"]: 4 * self.d_inner}),
                (self.d_inner, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.selective_scan import selective_scan_route

        hands = [layer.number for layer in layers if layer.hands_on]
        return {**selective_scan_route(step.seq_len or step.tokens,
                                       step.mesh),
                "layers": _numbers(layers), "d_inner": self.d_inner,
                "d_state": self.d_state, "conv": self.d_conv,
                "dt_rank": self.dt_rank,
                "hands_on": hands[0] if hands else []}


def _state_decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` as Mamba's published initialiser has it: A[d, n] = n + 1."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _taps_init(key, shape, dtype=jnp.float32):
    """U(-1/2, 1/2): a depthwise convolution's default at 4 taps, for the
    taps and their bias."""
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


class StateSpaceMixer(nn.Module):
    """Mamba-1 (arXiv:2312.00752) over ``spec.d_inner`` channels: (x, z) =
    u W_in; x = silu(conv(x) + b), a causal depthwise convolution of
    ``d_conv`` taps; (delta, B, C) = x W_x, split dt_rank / d_state /
    d_state; Delta = softplus(delta W_dt + b_dt); A = -exp(A_log); the
    selective scan (ops/selective_scan.py, the one rule there names its
    route; a recurrence, no positions) with y += D x; out = (y silu(z))
    W_out. Returns (out, y): y, BEFORE the gate, is what the memory layer
    hands on. Element-wise work, the convolution and the scan in float32;
    x_proj and dt_proj float32 at matmul precision highest (they make the
    scan's steps, whose decays exp(Delta A) reach A = -16), as a linear
    layer's gates are. The four projections' products carry the names of
    the spec's ``KEPT``."""

    d_model: int
    spec: StateSpaceSpec

    @nn.compact
    @trace.scope("ssm")
    def __call__(self, u):
        from metaopt_tpu.ops.selective_scan import selective_scan

        sp, kept = self.spec, self.spec.KEPT
        own = lambda name, init, shape, axes: self.param(  # noqa: E731
            name, with_mesh_partitioning(init, axes), shape)
        exact = lambda name, width, axes, **kw: nn.Dense(  # noqa: E731
            width, name=name, precision=jax.lax.Precision.HIGHEST,
            kernel_init=with_mesh_partitioning(
                nn.initializers.lecun_normal(), axes), **kw)
        xz = checkpoint_name(nn.DenseGeneral(
            (2, sp.d_inner), dtype=jnp.bfloat16, name="in_proj",
            use_bias=False, kernel_init=_pinit(True, (None, None, "tp")),
        )(u.astype(jnp.bfloat16)), kept["in"])
        x = jax.nn.silu(
            short_conv(xz[..., 0, :].astype(jnp.float32), own(
                "conv", _taps_init, (sp.d_conv, sp.d_inner), (None, "tp")))
            + own("conv_bias", _taps_init, (sp.d_inner,), ("tp",)))
        dbc = checkpoint_name(exact(
            "x_proj", sp.dt_rank + 2 * sp.d_state, ("tp", None),
            use_bias=False)(x), kept["x"])
        delta, b, c = jnp.split(
            dbc, [sp.dt_rank, sp.dt_rank + sp.d_state], axis=-1)
        dt = jax.nn.softplus(checkpoint_name(exact(
            "dt_proj", sp.d_inner, (None, "tp"), bias_init=_dt_bias_init,
        )(delta), kept["dt"]))
        a = -jnp.exp(own("A_log", _state_decay_init,
                         (sp.d_inner, sp.d_state), ("tp", None)))
        y = selective_scan(x, dt, a, b, c) \
            + own("D", nn.initializers.ones, (sp.d_inner,), ("tp",)) * x
        gated = y * jax.nn.silu(xz[..., 1, :].astype(jnp.float32))
        return checkpoint_name(nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="out_proj",
            use_bias=False, kernel_init=_pinit(True, ("tp", None)),
        )(gated.astype(jnp.bfloat16)), kept["out"]), y


@dataclasses.dataclass(frozen=True)
class MemoryUnitSpec(_Mixer):
    """A gated memory unit over a memory ``d_inner`` wide, which an earlier
    layer hands on."""

    d_inner: int

    KEPT = {"in": "gmu.in_proj", "out": "gmu.out_proj"}
    TP = ("d_inner",)

    attends = False
    kind = "gmu"

    def mix(self, block, x, memory):
        return GatedMemoryUnit(block.d_model, self.d_inner,
                               name="gmu")(x, memory), {}

    def kernel_keeps(self):
        return ()

    def products(self, d_model):
        kept = self.KEPT
        return [(d_model, {kept["in"]: 2 * self.d_inner}),
                (self.d_inner, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        return {"layers": _numbers(layers), "reads": sources["memory"],
                "d_inner": self.d_inner}


class GatedMemoryUnit(nn.Module):
    """out = (memory * silu(u W_in)) W_out: an earlier layer's scan output
    (``memory``, float32, ``d_inner`` wide) gated by this layer's own
    projection of its input. No bias. The two products carry the names of
    ``MemoryUnitSpec.KEPT``."""

    d_model: int
    d_inner: int

    @nn.compact
    @trace.scope("gmu")
    def __call__(self, u, memory):
        kept = MemoryUnitSpec.KEPT
        gate = checkpoint_name(nn.Dense(
            self.d_inner, dtype=jnp.bfloat16, name="in_proj", use_bias=False,
            kernel_init=_pinit(True, (None, "tp")),
        )(u.astype(jnp.bfloat16)), kept["in"])
        gated = memory * jax.nn.silu(gate.astype(jnp.float32))
        return checkpoint_name(nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="out_proj",
            use_bias=False, kernel_init=_pinit(True, ("tp", None)),
        )(gated.astype(jnp.bfloat16)), kept["out"])


@dataclasses.dataclass(frozen=True)
class DifferentialSpec(_Mixer):
    """Differential attention as a description has it: the query and K/V
    heads held here (pairs of them) and their width, a ``window`` or None,
    the layer's ``lambda_init``, and ``cross``: the layer reads another
    layer's K and V and has no k and v projections of its own. No
    positions."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    lambda_init: float
    cross: bool

    KEPT = _ATTENTION_KEPT
    TP = ("heads", "kv_heads")

    def mix(self, block, x, *kv):
        out, kv = DifferentialAttention(block.d_model, self, block.eps,
                                        name="attn")(x, *kv)
        return out, {"kv": kv}

    def products(self, d_model):
        kept, kv = self.KEPT, 2 * self.kv_heads * self.head_dim
        own = {} if self.cross else {kept["k"]: kv, kept["v"]: kv}
        return [(d_model, {kept["q"]: 2 * self.heads * self.head_dim,
                           **own}),
                (self.heads * self.head_dim, {kept["out"]: 2 * d_model})]

    @property
    def kind(self) -> str:
        return ("cross" if self.cross else
                "window" if self.window else "global") + "-nope"

    def describe(self, step, layers, sources):
        said = {"route": step.route,
                "mask": _mask_said(step.route, self.window),
                **_kernels_said(step, self.window),
                "layers": _numbers(layers),
                "differential": [self.heads // 2, self.kv_heads // 2,
                                 self.head_dim, 2 * self.head_dim]}
        if self.cross:
            said["reads"] = sources["kv"]
        return said


class DifferentialAttention(nn.Module):
    """Differential attention (arXiv:2410.05258) under a causal mask, with
    bias on the projections. The ``spec.heads`` query heads are pairs (2p,
    2p + 1) = (q_1, q_2), the ``kv_heads`` K/V heads pairs (2j, 2j + 1) =
    (k_1, k_2) whose two values are joined to one v twice as wide; query
    pair p reads K/V pair p // (query pairs / K/V pairs). a_i =
    softmax(q_i k_i^T / sqrt(head_dim) + mask) v; o = (1 - lambda_init)
    rmsnorm(a_1 - lambda a_2) over the joined width, lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init; then the output
    projection. ``kv``: another layer's (k, v) after its projection, read in
    place of this layer's own (a cross layer, which then has no k and v
    projections). Returns (out, (k, v)).

    The pairs reach the ``CausalMask`` kernels as two calls, q_1 on k_1 and
    q_2 on k_2 (every other head: the kernels' grouping, query head h on
    K/V head h // group, is then the pairs' own order), both on the one
    joined v, which is a reshape and no copy; the kernels run at q.k
    ``head_dim`` and v 2 ``head_dim`` wide. The combination is under the
    scope ``attention.diff``."""

    d_model: int
    spec: DifferentialSpec
    eps: float

    @nn.compact
    @trace.scope("attention")
    def __call__(self, u, kv=None):
        sp, kept = self.spec, self.spec.KEPT
        proj = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, sp.head_dim), axis=-1, dtype=jnp.bfloat16, name=name,
            kernel_init=_pinit(True, (None, "tp", None)))
        u = u.astype(jnp.bfloat16)
        q = checkpoint_name(proj("q", sp.heads)(u), kept["q"])
        if kv is None:
            kv = (checkpoint_name(proj("k", sp.kv_heads)(u), kept["k"]),
                  checkpoint_name(proj("v", sp.kv_heads)(u), kept["v"]))
        k, v = kv
        q = (q / math.sqrt(sp.head_dim)).astype(jnp.bfloat16)
        joined = v.reshape(*v.shape[:2], -1, 2 * sp.head_dim)
        mask = CausalMask(sp.window)
        a1, a2 = (attend(q[:, :, i::2], k[:, :, i::2], joined, mask)
                  for i in (0, 1))
        with trace.scope("attention.diff"):
            lam = lambda name: self.param(  # noqa: E731
                name, nn.initializers.normal(0.1), (sp.head_dim,))
            weight = jnp.exp(jnp.sum(lam("lambda_q1") * lam("lambda_k1"))) \
                - jnp.exp(jnp.sum(lam("lambda_q2") * lam("lambda_k2"))) \
                + sp.lambda_init
            out = RMSNorm(self.eps, name="subln")(
                a1.astype(jnp.float32) - weight * a2.astype(jnp.float32)) \
                * (1.0 - sp.lambda_init)
        return checkpoint_name(nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=jnp.bfloat16, name="out",
            kernel_init=_pinit(True, ("tp", None, None)),
        )(out.astype(jnp.bfloat16)), kept["out"]), kv


# ---------------------------------------------------------------------------
# A grouped layer's q and k, from the projections' products to attention's
# operands. Down here because a line that moves above ``LatentSpec`` moves
# the latent layer's frames above its Pallas calls, and with them that
# cell's compiled kernels (ROADMAP S11).


class NormScale(nn.Module):
    """An RMS norm's scale vector alone, under the name and at the path
    :class:`RMSNorm` gives it, for a norm that a kernel applies."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.initializers.ones, (width,))


def _handed_over(layer: GroupedAttention, q, k):
    """(q, k) as attention takes them, bfloat16, from the products of
    ``layer``'s projections: the RMS norms its spec says, rotary by its
    rule, q over sqrt(head_dim), float32 until one rounding. How is
    ops/grouped_hand_over.hand_over's to say: ``"one pass"``, a Pallas call
    an operand and direction, or ``"passes"``, XLA's elementwise passes,
    which is also the tests' oracle for the first."""
    from metaopt_tpu.ops import grouped_hand_over as gh
    from metaopt_tpu.parallel.mesh import active_mesh

    sp, mesh = layer.spec, active_mesh()
    if gh.hand_over(attention_route(0.0, mesh), mesh, sp.qk_norm,
                    sp.theta is not None) == "one pass":
        scale = lambda name: NormScale(name=name)(  # noqa: E731
            sp.head_dim) if sp.qk_norm else None
        cos = sin = None
        if sp.rule:
            cos, sin = gh.tables(
                sp.rule.frequencies(sp.rule.turned or sp.head_dim),
                sp.rule.factor, q.shape[1])
        return (gh.operand(q, scale("q_norm"), cos, sin, layer.eps,
                           1.0 / math.sqrt(sp.head_dim)),
                gh.operand(k, scale("k_norm"), cos, sin, layer.eps, 1.0))
    if sp.qk_norm is not None:
        whole = lambda y: y.reshape(  # noqa: E731
            *y.shape[:2], -1) if sp.qk_norm == "whole" else y
        q = RMSNorm(layer.eps, name="q_norm")(whole(q)).reshape(q.shape)
        k = RMSNorm(layer.eps, name="k_norm")(whole(k)).reshape(k.shape)
    if sp.theta is not None:
        q, k = rope(q, sp.rule), rope(k, sp.rule)
    return ((q / math.sqrt(sp.head_dim)).astype(jnp.bfloat16),
            k.astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# A Mamba-2 mixer, a feed-forward of two matrices and the sublayer a block
# does not have. Down here for the reason :func:`_handed_over` is.


#: a published config's activation by its name there; ``relu2``: the
#: squared ReLU of a feed-forward that is not gated
ACTIVATIONS = {"relu": nn.relu, "silu": nn.silu,
               "relu2": lambda x: jnp.square(nn.relu(x))}


class PlainFeedForward(nn.Module):
    """act(x W_up) W_down, no gate and no bias: :class:`GatedFeedForward`
    of two matrices, its products under the same names of
    ``GatedSpec.KEPT`` and the up product behind the same barrier."""

    d_model: int
    d_ff: int
    activation: str = "relu2"

    @nn.compact
    @trace.scope("ffn")
    def __call__(self, x):
        dense = lambda name, n, axes: nn.Dense(  # noqa: E731
            n, dtype=jnp.bfloat16, name=name, use_bias=False,
            kernel_init=_pinit(True, axes))
        kept = GatedSpec.KEPT
        up = jax.lax.optimization_barrier(checkpoint_name(
            dense("up", self.d_ff, (None, "tp"))(x.astype(jnp.bfloat16)),
            kept["up"]))
        return checkpoint_name(dense("down", self.d_model, ("tp", None))(
            ACTIVATIONS[self.activation](up)), kept["down"])


@dataclasses.dataclass(frozen=True)
class Absent(_Spec):
    """The sublayer a block does not have (a pattern of one sublayer a
    block: a mixer OR a feed-forward): no branch, no norm, no products, no
    counts, no line in ``trial.setup``. models/lm.py::PatternBlock, the
    pattern's kinds and the span pass it over by its type."""

    kind = "absent"

    def before_mixer(self, block, n):
        return None

    def products(self, d_model):
        return []

    def describe(self, step, layers, sources):
        return {}


@dataclasses.dataclass(frozen=True)
class ScalarDecaySpec(_Mixer):
    """A Mamba-2 mixer's sizes (arXiv:2405.21060): ``heads`` heads of
    ``head_dim`` channels, B and C of ``state`` numbers shared by the heads
    of a group (``groups`` of them), a convolution of ``conv`` taps with a
    bias. On a ``tp`` axis the mixer is whole on every device: its one
    input projection joins z, x, B, C and dt and has no one axis to cut.
    ``describe`` says under ``"hand_over"`` which form the float32 work
    around the scan takes (ops/ssd_hand_over.hand_over, the one rule):
    ``"one pass"``, a Pallas call a side and direction, on the Pallas route
    of one device at widths of whole lanes; ``"passes"``, XLA's,
    elsewhere."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int

    #: the input projection's product (z | x B C before the convolution),
    #: the steps' (float32, a number a head: before the bias and the
    #: softplus) and the output projection's
    KEPT = {"in": "ssd.in_proj", "dt": "ssd.dt_proj", "out": "ssd.out_proj"}

    attends = False
    kind = "ssd"

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def sizes(self):
        """The sizes as ops/ssd_hand_over.py's rule and calls take them."""
        from metaopt_tpu.ops.ssd_hand_over import Sizes

        return Sizes(self.heads, self.head_dim, self.groups, self.state)

    def mix(self, block, x):
        return ScalarDecayMixer(block.d_model, self, block.eps,
                                name="ssd")(x), {}

    def kernel_keeps(self):
        from metaopt_tpu.ops import linear_attention

        return linear_attention.SCALAR_DECAY_KEEPS

    def products(self, d_model):
        kept = self.KEPT
        return [(d_model, {kept["in"]: 2 * 2 * (
                     self.d_inner + self.groups * self.state),
                           kept["dt"]: 4 * self.heads}),
                (self.d_inner, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.linear_attention import linear_attention_route
        from metaopt_tpu.ops.ssd_hand_over import hand_over

        route = linear_attention_route()
        return {**route, "hand_over": hand_over(route["route"], step.mesh,
                                                self.sizes),
                "layers": _numbers(layers),
                "heads": self.heads, "head_dim": self.head_dim,
                "groups": self.groups, "state": self.state,
                "conv": self.conv,
                "norm_group": self.d_inner // self.groups,
                "program": f"group of {self.heads // self.groups} heads "
                           "and chunk",
                "remat_keeps": list(self.kernel_keeps())}


def _head_decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` as Mamba-2's published initialiser draws it: log of
    U(1, 16), a number a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class ScalarDecayMixer(nn.Module):
    """Mamba-2 (arXiv:2405.21060; the ``nemotron_h`` family's mixer) over
    ``spec.heads`` heads: [z | xBC | dt] = u W_in, one projection, no bias;
    xBC = silu(conv(xBC) + b), a causal depthwise convolution of ``conv``
    taps; x (heads x head_dim), B, C (groups x state) split from it; dt =
    softplus(dt + dt_bias) and a = -exp(A_log), a number a head; head h
    reads group h // (heads / groups); H_t = exp(dt_t a) H_{t-1} + dt_t x_t
    B_t^T, y_t = H_t C_t + D x_t: the scalar decay rule
    (ops/linear_attention.py: q = C, k = B, v = dt x, g = dt a; the one
    rule there names its route; a recurrence, no positions); y =
    rmsnorm(y silu(z)) with the mean square over each group's d_inner /
    groups channels (the gate BEFORE the norm); out = y W_out. Elementwise
    work, the convolution, the decays and the norm in float32; dt's
    columns of W_in are multiplied in float32 at matmul precision highest
    (``heads`` columns: the decays exp(dt a) compound over a row), as a
    linear layer's gates are. The three products carry the names of the
    spec's ``KEPT``. Between the input projection's products and the rule,
    and between the rule's output and ``out_proj``, the form is
    ops/ssd_hand_over.hand_over's to say: ``"one pass"`` on the Pallas
    route of one device (``ssd_operands`` in front, ``ssd_gated_norm``
    behind, x made again from the product there, every float32 number
    float32 in the calls and every rounding where it was), ``"passes"``
    (:meth:`_passes`: XLA's elementwise passes) off the TPU, on a mesh of
    several devices and at widths that are no whole lanes."""

    d_model: int
    spec: ScalarDecaySpec
    eps: float

    @nn.compact
    @trace.scope("ssd")
    def __call__(self, u):
        from metaopt_tpu.ops import ssd_hand_over as sh
        from metaopt_tpu.ops.linear_attention import (linear_attention_route,
                                                      scalar_decay_rule)
        from metaopt_tpu.parallel.mesh import active_mesh

        sp, kept = self.spec, self.spec.KEPT
        own = lambda name, init, shape: self.param(  # noqa: E731
            name, with_mesh_partitioning(init, (None,) * len(shape)), shape)
        inner, bc = sp.d_inner, sp.groups * sp.state
        w_in = own("in_proj", nn.initializers.lecun_normal(),
                   (self.d_model, 2 * inner + 2 * bc + sp.heads))
        zxbc = checkpoint_name(jnp.dot(
            u.astype(jnp.bfloat16), w_in[:, :-sp.heads].astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16), kept["in"])
        dt = checkpoint_name(jnp.dot(
            u.astype(jnp.float32), w_in[:, -sp.heads:],
            precision=jax.lax.Precision.HIGHEST), kept["dt"])
        dt_bias = own("dt_bias", _dt_bias_init, (sp.heads,))
        taps = own("conv", _taps_init, (sp.conv, inner + 2 * bc))
        conv_bias = own("conv_bias", _taps_init, (inner + 2 * bc,))
        a_log = own("A_log", _head_decay_init, (sp.heads,))
        skip = own("D", nn.initializers.ones, (sp.heads,))
        weight = own("norm", nn.initializers.ones, (inner,))
        sizes = sp.sizes
        if sh.hand_over(linear_attention_route()["route"], active_mesh(),
                        sizes) == "one pass":
            c, b, v, g, z, x = sh.ssd_operands(
                zxbc, dt, taps, conv_bias, dt_bias, a_log, sizes)
            normed = sh.ssd_gated_norm(
                scalar_decay_rule(c, b, v, g), z, x,
                jax.lax.stop_gradient(zxbc), taps, conv_bias, skip, weight,
                sizes, self.eps)
        else:
            normed = self._passes(zxbc, jax.nn.softplus(dt + dt_bias), taps,
                                  conv_bias, a_log, skip, weight)
        return checkpoint_name(nn.Dense(
            self.d_model, dtype=jnp.bfloat16, name="out_proj",
            use_bias=False, kernel_init=_pinit(True, (None, None)),
        )(normed), kept["out"])

    def _passes(self, zxbc, dt, taps, conv_bias, a_log, skip, weight):
        """The output projection's operand by XLA's elementwise passes
        around the scan, float32 between the roundings: the form off the
        Pallas route and on a mesh of several devices, and the oracle of
        ops/ssd_hand_over.py's calls."""
        from metaopt_tpu.ops.linear_attention import scalar_decay_rule

        sp = self.spec
        inner, bc = sp.d_inner, sp.groups * sp.state
        xbc = jax.nn.silu(
            short_conv(zxbc[..., inner:].astype(jnp.float32), taps)
            + conv_bias)
        heads = lambda y, n: y.reshape(*y.shape[:2], n, -1)  # noqa: E731
        x = heads(xbc[..., :inner], sp.heads)
        b, c = (heads(xbc[..., inner + i * bc:inner + (i + 1) * bc],
                      sp.groups).astype(jnp.bfloat16) for i in (0, 1))
        a = -jnp.exp(a_log)
        y = scalar_decay_rule(c, b, (dt[..., None] * x).astype(jnp.bfloat16),
                              dt * a).astype(jnp.float32) \
            + skip[:, None] * x
        gated = y.reshape(zxbc.shape[:2] + (inner,)) * jax.nn.silu(
            zxbc[..., :inner].astype(jnp.float32))
        grouped = heads(gated, sp.groups)
        normed = (grouped * jax.lax.rsqrt(jnp.mean(
            jnp.square(grouped), axis=-1, keepdims=True) + self.eps)
        ).reshape(gated.shape) * weight
        return normed.astype(jnp.bfloat16)


def _delta_form(spec: LinearSpec, route: str, mesh) -> str:
    """ops/delta_hand_over.hand_over's answer for a mixer of ``spec``."""
    from metaopt_tpu.ops.delta_hand_over import Sizes, hand_over

    return hand_over(route, mesh, Sizes(spec.heads, spec.key_dim,
                                        spec.value_dim, spec.conv))


def _delta_mixed(layer: LinearAttention, mixed, g, beta, gate):
    """The output projection's operand (B, T, H, value_dim) bfloat16 of a
    gated-delta mixer from ``mixed``, its q, k and v (product, taps) by
    name, the log decays, the steps and the g product. How is
    ops/delta_hand_over.hand_over's to say: ``"one pass"``
    (``delta_operands`` in front of the scan's heads-first door,
    ``delta_gated_norm`` behind it: every float32 number float32 in the
    calls and every rounding where it was) on the Pallas route of one
    device, ``"passes"`` (XLA's element-wise passes around
    ``gated_delta_rule``, float32 between the roundings) off the TPU, on a
    mesh of several devices and at a rehearsal's widths; the second is also
    the tests' oracle for the first. Down here for the reason
    :func:`_handed_over` is."""
    from metaopt_tpu.ops import delta_hand_over as dh
    from metaopt_tpu.ops.linear_attention import (gated_delta_rule,
                                                  linear_attention_route)
    from metaopt_tpu.parallel.mesh import active_mesh

    sp = layer.spec
    if _delta_form(sp, linear_attention_route()["route"],
                   active_mesh()) == "one pass":
        (q, tq), (k, tk), (v, tv) = (mixed[n] for n in "qkv")
        o = dh.gated_delta_rule_heads_first(
            *dh.delta_operands(q, k, v, tq, tk, tv), g, beta)
        return dh.delta_gated_norm(o, gate, NormScale(name="norm")(
            sp.value_dim), layer.eps)
    q, k, v = (jax.nn.silu(short_conv(p.astype(jnp.float32), t))
               for p, t in (mixed[n] for n in "qkv"))
    unit = lambda y: y * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    o = gated_delta_rule(
        (unit(q) * sp.key_dim ** -0.5).astype(jnp.bfloat16),
        unit(k).astype(jnp.bfloat16), v.astype(jnp.bfloat16), g, beta)
    return (RMSNorm(layer.eps, name="norm")(o) * jax.nn.silu(
        gate.astype(jnp.float32))).astype(jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class ShortConvSpec(_Mixer):
    """A gated short convolution as a mixer (the ``lfm2_moe`` family's
    ``conv`` layers): ``channels`` channels, ``taps`` taps, no bias and no
    activation. On a ``tp`` axis the mixer is whole on every device: its one
    input projection joins the three thirds B, C and X, which meet channel
    by channel, and has no one axis to cut; ``describe`` says so, and which
    route the core takes (ops/short_conv.short_conv_route, the one rule).
    Down here for the reason :func:`_handed_over` is."""

    channels: int
    taps: int

    #: the input projection's product ([B | C | X] before the gates and the
    #: convolution, which are made again from it) and the output
    #: projection's
    KEPT = {"in": "short_conv.in_proj", "out": "short_conv.out_proj"}

    attends = False
    kind = "short-conv"

    def mix(self, block, x):
        return ShortConvMixer(block.d_model, self, name="conv")(x), {}

    def kernel_keeps(self):
        return ()  # the core is one pass over the product: made again

    def products(self, d_model):
        kept = self.KEPT
        return [(d_model, {kept["in"]: 2 * 3 * self.channels}),
                (self.channels, {kept["out"]: 2 * d_model})]

    def describe(self, step, layers, sources):
        from metaopt_tpu.ops.short_conv import short_conv_route

        return {"route": short_conv_route(step.mesh, self.channels,
                                          step.seq_len or 0),
                "layers": _numbers(layers), "channels": self.channels,
                "taps": self.taps, "tp": "whole on every device"}


class ShortConvMixer(nn.Module):
    """The gated short convolution of the ``lfm2_moe`` family (LFM2,
    arXiv:2511.23404): [B | C | X] = u W_in, ONE projection d_model -> 3 x
    ``channels``, the thirds in this order, no bias; y_t = C_t * sum_i
    taps[i] (B X)_{t - (K - 1) + i}, a causal depthwise convolution of K =
    ``spec.taps`` taps between two gates read from the layer's own input
    ((B X) before the row's start = 0; no bias, NO activation); out = y
    W_out. No state beyond K - 1 tokens, no scan, no positions. The
    products bfloat16 with float32 accumulation; the gates and the taps'
    sum float32, one rounding to the output projection's operand (the core,
    under ``short_conv.core``: ops/short_conv.py's two Pallas calls on one
    TPU device, their plain twin elsewhere: ``short_conv_route`` is the one
    rule). The two products carry the names of the spec's ``KEPT``."""

    d_model: int
    spec: ShortConvSpec

    @nn.compact
    @trace.scope("short_conv")
    def __call__(self, u):
        from metaopt_tpu.ops import short_conv as sc
        from metaopt_tpu.parallel.mesh import active_mesh

        sp, kept = self.spec, self.spec.KEPT
        dense = lambda name, n: nn.Dense(  # noqa: E731
            n, dtype=jnp.bfloat16, name=name, use_bias=False,
            kernel_init=_pinit(True, (None, None)))
        bcx = checkpoint_name(dense("in_proj", 3 * sp.channels)(
            u.astype(jnp.bfloat16)), kept["in"])
        taps = self.param("conv", with_mesh_partitioning(
            _taps_init, (None, None)), (sp.taps, sp.channels))
        if sc.short_conv_route(active_mesh(), sp.channels,
                               u.shape[1]) == "pallas":
            y = sc.gated_short_conv(bcx, taps)
        else:
            with trace.scope("short_conv.core"):
                y = sc.gated_short_conv_plain(bcx, taps)
        return checkpoint_name(dense("out_proj", self.d_model)(y),
                               kept["out"])
