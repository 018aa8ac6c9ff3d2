"""From a description's published words to a :class:`Pattern`: the one
module of the zoo in which a family's name appears.

A description is the hyperparameters of models/lm.py::make_lm: the zoo's
own keys (``d_model``, ``n_layers`` ...) or a published config's
(``_PUBLISHED``), plus the chip's share of a deployment: ``experts_held`` =
(first, count) of the routed experts, ``vocab_held`` = (first, count) of the
vocabulary's rows, ``heads_held`` = (first, count) of
``num_attention_heads`` (every kind of head is built in that proportion;
``laguna``, whose layers differ in their head counts, takes none, and
neither do ``nemotron_h`` and ``lfm2_moe``, whose deployments share no
heads) and, for ``phi4flash``, ``nemotron_h`` and ``lfm2_moe``,
``layers_held``: the published numbers of the layers this chip holds (a
pipeline stage's; not the first n). Eight families have a reader.
:func:`family_of` says
whose words a description speaks, and the family's reader (``_FAMILIES``)
builds each held layer's specs (models/lm_layers.py, models/moe.py)
directly. What has no layer here is refused by its name.
:func:`describe_pattern` joins the specs' parts of ``trial.setup``'s
``attrs``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

from jax.sharding import Mesh

from metaopt_tpu.models.lm_layers import (
    Absent, DifferentialSpec, GatedSpec, GroupedSpec, LatentSpec, LinearSpec,
    MemoryUnitSpec, Rotary, ScalarDecaySpec, ShortConvSpec, StateSpaceSpec)
from metaopt_tpu.models.moe import RoutedSpec, RoutingRule
from metaopt_tpu.ops.embed import embed_gradient_route

#: a description's published names beside the zoo's own
_PUBLISHED = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_hidden_layers": "n_layers", "vocab_size": "vocab",
              # the Qwen3-MoE family's words
              "num_experts": "moe_num_primary_experts",
              "num_experts_per_tok": "moe_num_active_primary_experts",
              "moe_intermediate_size": "moe_ffn_hidden_size",
              # the Olmo hybrid family's
              "intermediate_size": "d_ff",
              # the DeepSeek-V3 family's
              "n_routed_experts": "moe_num_primary_experts"}

#: a published ``layer_types`` entry -> is the layer linear?
_LAYER_TYPES = {"linear_attention": True, "full_attention": False}


@dataclasses.dataclass(frozen=True)
class Layer:
    """One held layer: its published ``number`` (its block is ``h{number}``),
    its mixer's and its feed-forward's spec, and the names of what it
    ``reads`` of earlier layers and ``hands_on`` to later ones (of what its
    mixer offers: a state-space mixer its scan output, ``"memory"``, a
    differential layer its K and V, ``"kv"``)."""

    number: int
    mixer: Any
    ffn: Any
    reads: Tuple[str, ...] = ()
    hands_on: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class Pattern:
    """What a description with a layer pattern says: the held ``layers``,
    the norm (a key of models/lm_layers.py::NORMS) and its ``eps``, the
    chip's share of the vocabulary (ids are drawn from that slice, and
    logits and loss are over it) and of ``num_attention_heads`` ((first,
    count) each; None: all the heads), and whether the head is the
    embedding's table."""

    layers: Tuple[Layer, ...]
    norm: str
    eps: float
    vocab_held: Tuple[int, int]
    heads_held: Optional[Tuple[int, int]]
    tied: bool

    def kind(self, i: int) -> str:
        return self.layers[i].mixer.kind

    def kinds(self):
        """The distinct layer kinds, in the pattern's order."""
        return list(dict.fromkeys(layer.mixer.kind for layer in self.layers))

    def by_kind(self):
        """[(kind, its layers)] of the layers that have a mixer, the kinds
        that attend before the
        recurrences, each group in the pattern's order: the order in which
        ``trial.setup`` lists them and the remat rule tries their
        products."""
        groups: Dict[str, list] = {}
        for layer in self.layers:
            if not isinstance(layer.mixer, Absent):
                groups.setdefault(layer.mixer.kind, []).append(layer)
        return sorted(groups.items(),
                      key=lambda group: not group[1][0].mixer.attends)

    def under_tp(self, tp: int) -> "Pattern":
        """The pattern as one device of a ``tp`` mesh axis holds it."""
        return dataclasses.replace(self, layers=tuple(
            dataclasses.replace(layer, mixer=layer.mixer.under_tp(tp),
                                ffn=layer.ffn.under_tp(tp))
            for layer in self.layers))


@dataclasses.dataclass(frozen=True)
class Step:
    """What ``trial.setup`` describes a pattern for: steps of ``tokens``
    tokens in rows of ``seq_len`` on attention route ``route`` under
    ``mesh``, for a description ``d_model`` wide whose dense feed-forward is
    ``d_ff``."""

    route: str
    mesh: Any
    tokens: int
    seq_len: Optional[int]
    d_model: int
    d_ff: int


def _own_names(hparams: Dict[str, Any]) -> Dict[str, Any]:
    h = dict(hparams)
    for published, own in _PUBLISHED.items():
        if published in h:
            h.setdefault(own, h[published])
    return h


def family_of(h: Dict[str, Any]) -> Optional[str]:
    """The family whose words a description with a layer pattern speaks
    (a key of ``_FAMILIES``, eight of them), None for one without a
    pattern. ``model_type`` is asked FIRST, for the families that name
    themselves and speak other families' words besides: ``phi4flash``;
    ``laguna`` (the Olmo hybrid's ``layer_types`` and the Qwen3-MoE
    family's ``num_experts`` at once); ``lfm2_moe`` (the same two keys, and
    a ``layer_types`` entry, ``conv``, that the Olmo hybrid's reader refuses
    by name). Then the keys, in this order: ``hybrid_override_pattern`` is
    ``nemotron_h``'s (which speaks the DeepSeek-V3 family's routing words
    too, so it is asked before them), ``kv_lora_rank`` the DeepSeek-V3
    family's, ``layer_types`` the Olmo hybrid's, ``num_experts`` the
    Qwen3-MoE family's, the two layouts or ``sa_config`` alone
    SmallThinker's."""
    if h.get("model_type") in ("phi4flash", "laguna", "lfm2_moe"):
        return h["model_type"]
    for key, family in (("hybrid_override_pattern", "nemotron_h"),
                        ("kv_lora_rank", "deepseek_v3"),
                        ("layer_types", "olmo_hybrid"),
                        ("num_experts", "qwen3_moe"),
                        ("sa_config", "layouts"),
                        ("rope_layout", "layouts"),
                        ("sliding_window_layout", "layouts")):
        if key in h:
            return family
    return None


def pattern_of(h: Dict[str, Any]) -> Optional[Pattern]:
    """The layer pattern a description names, or None: what its family's
    reader builds of it."""
    family = family_of(h)
    return None if family is None else _FAMILIES[family](h)


# -- what several families say in the same words -----------------------------

def _held(h, key: str, whole: int):
    return tuple(int(v) for v in h.get(key) or (0, whole))


def _layers_held(h, of: int) -> Tuple[int, ...]:
    """The published numbers of the layers held, of a model ``of`` deep:
    ``layers_held`` (a pipeline stage's), or all of them."""
    numbers = tuple(int(n) for n in h.get("layers_held") or range(of))
    if list(numbers) != sorted(set(numbers)) or not numbers \
            or not 0 <= numbers[0] <= numbers[-1] < of:
        raise ValueError(f"layers_held {list(numbers)}: the published "
                         f"numbers of layers 0..{of - 1}, ascending")
    return numbers


def _heads(h):
    """(the query heads held here, ``share``: a published head count ->
    the count held in that proportion)."""
    n_heads = int(h.get("n_heads", 8))
    held = _held(h, "heads_held", n_heads)[1]
    return held, lambda heads: int(heads) * held // n_heads


def _pattern(h, layers, norm: str, eps_key: str = "rms_norm_eps",
             eps: float = 1e-6, tied: bool = False) -> Pattern:
    n_heads = int(h.get("n_heads", 8))
    return Pattern(
        layers=tuple(layers), norm=norm, eps=float(h.get(eps_key, eps)),
        vocab_held=_held(h, "vocab_held", int(h.get("vocab", 1000))),
        heads_held=_held(h, "heads_held", n_heads) if "heads_held" in h
        else None, tied=tied)


def _theta(h) -> Optional[float]:
    return (h.get("rope_parameters") or h).get("rope_theta", 10000.0)


def _gated(h) -> GatedSpec:
    return GatedSpec(int(h.get("d_ff", 2048)), str(h.get("hidden_act",
                                                         "relu")))


def _expert_width(h) -> int:
    return int(h.get("moe_ffn_hidden_size", h.get("d_ff", 2048)))


def _feed_forward(h, router_after_mixer: bool, rule=RoutingRule(),
                  shared_d_ff: int = 0):
    """A routed feed-forward where the description names experts (beside
    shared ones, one feed-forward ``shared_d_ff`` wide), else the gated
    one."""
    n_experts = int(h.get("moe_num_primary_experts", 0))
    if not n_experts:
        return _gated(h)
    return RoutedSpec(
        n_experts=n_experts,
        top_k=int(h.get("moe_num_active_primary_experts", 1)),
        d_ff=_expert_width(h), held=_held(h, "experts_held", n_experts),
        activation=str(h.get("hidden_act", "relu")),
        shared_d_ff=shared_d_ff, rule=rule,
        router_after_mixer=router_after_mixer)


def _grouped_layers(h, qk_norm: Optional[str], types=None):
    """[a :class:`GroupedSpec` a layer], as the two layouts say (read up
    to ``n_layers``: a cut in depth keeps the leading layers; without them
    every layer is global and rotary; with ``rope_parameters.rope_theta``
    null, no positions anywhere), with the ``sa_config``'s selection."""
    n_layers = int(h.get("n_layers", 6))
    theta = _theta(h)
    rotary = list(h.get("rope_layout") or [int(theta is not None)] * n_layers)
    sliding = list(h.get("sliding_window_layout") or [0] * n_layers)
    if min(len(rotary), len(sliding), len(types or rotary)) < n_layers:
        raise ValueError(f"the layouts name {len(rotary)}, {len(sliding)} "
                         f"and {len(types or rotary)} layers, the model has "
                         f"{n_layers}")
    theta = float(10000.0 if theta is None else theta)
    window = int(h.get("sliding_window_size", 4096))
    whole = GroupedSpec(**_attention_heads(h), window=None, theta=None,
                        qk_norm=qk_norm,
                        selection=_selection(h.get("sa_config")))
    return [dataclasses.replace(whole, window=window if s else None,
                                theta=theta if r else None)
            for s, r in zip(sliding[:n_layers], rotary[:n_layers])]


def _attention_heads(h) -> Dict[str, int]:
    """The query and K/V heads held here and their width."""
    heads, share = _heads(h)
    return dict(
        heads=heads,
        kv_heads=share(h.get("num_key_value_heads", h.get("n_heads", 8))),
        head_dim=int(h.get("head_dim") or int(h.get("d_model", 512))
                     // int(h.get("n_heads", 8))))


def _selection(sa: Optional[Dict[str, Any]]):
    """(index heads, their width, top k) of a published ``sa_config``; its
    chunk sizes tile the computation and do not change the result."""
    if not sa:
        return None
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("the indexer has one key head, not "
                         f"{sa['indexer_num_kv_heads']}")
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


# -- a reader a family --------------------------------------------------------

def _layouts(h, qk_norm=None, router_after_mixer=False) -> Pattern:
    """SmallThinker's: RMS norms before the branches, the router read
    BEFORE attention, experts or a feed-forward gated by ``hidden_act``
    (ReLU unless named), an untied head."""
    ffn = _feed_forward(h, router_after_mixer)
    return _pattern(h, [Layer(i, mixer, ffn) for i, mixer in
                        enumerate(_grouped_layers(h, qk_norm))], "rms")


def _qwen3_moe(h) -> Pattern:
    """The Qwen3-MoE family's: SmallThinker's layer with RMS norms of q and
    k over a head's width and the router read AFTER attention, from the
    second norm."""
    return _layouts(h, qk_norm="head", router_after_mixer=True)


def _olmo_hybrid(h) -> Pattern:
    """The Olmo hybrid family's: ``layer_types`` says which layers are
    linear (the gated delta rule) and which full attention, with RMS norms
    of q and k over the projected width; x + norm(mixer(x)) then x +
    norm(ffn(x)); the feed-forward gated by ``hidden_act``."""
    types = h.get("layer_types")
    unknown = sorted(set(types or ()) - set(_LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer_types names {unknown}; known: "
                         f"{sorted(_LAYER_TYPES)}")
    full = _grouped_layers(h, "whole", types)
    linear = [_LAYER_TYPES[t] for t in (types or ())[:len(full)]] \
        or [False] * len(full)
    spec = _linear(h, _heads(h)[1]) if any(linear) else None
    return _pattern(h, [Layer(i, spec if lin else full[i], _gated(h))
                        for i, lin in enumerate(linear)],
                    "rms on the branches")


def _linear(h: Dict[str, Any], share) -> LinearSpec:
    """The linear layers of a description in the Olmo hybrid family's
    words, ``share`` of each kind of head held."""
    heads = int(h["linear_num_value_heads"])
    if int(h.get("linear_num_key_heads", heads)) != heads:
        raise ValueError("a linear layer has as many key heads as value "
                         f"heads here, not {h['linear_num_key_heads']} and "
                         f"{heads}")
    return LinearSpec(
        heads=share(heads), of=heads, key_dim=int(h["linear_key_head_dim"]),
        value_dim=int(h["linear_value_head_dim"]),
        conv=int(h.get("linear_conv_kernel_dim", 4)),
        neg_eigval=bool(h.get("linear_allow_neg_eigval", False)))


def _deepseek_v3(h) -> Pattern:
    """The DeepSeek-V3 family's: every layer's attention is latent; the
    first ``first_k_dense_replace`` layers take a gated feed-forward of
    ``intermediate_size`` and the others route over ``n_routed_experts`` by
    the family's rule (:func:`_routing`), the router read after attention,
    beside ``n_shared_experts`` shared ones."""
    n_layers = int(h.get("n_layers", 6))
    dense = min(int(h.get("first_k_dense_replace", 0)), n_layers)
    mixer = _latent(h)
    routed = _feed_forward(h, True, _routing(h), int(
        h.get("n_shared_experts") or 0) * _expert_width(h))
    return _pattern(h, [Layer(i, mixer, _gated(h) if i < dense else routed)
                        for i in range(n_layers)], "rms")


def _latent(h: Dict[str, Any]) -> LatentSpec:
    """The latent attention of a description in the DeepSeek-V3 family's
    words. What has no layer here is refused by its name: a q rank (and
    the norm that comes with it), rotary scaling (its ``mscale``)."""
    for key in ("q_lora_rank", "rope_scaling"):
        if h.get(key) is not None:
            raise ValueError(f"{key} {h[key]!r}: a latent layer has none "
                             "here")
    theta = _theta(h)
    return LatentSpec(
        heads=_heads(h)[0], rank=int(h["kv_lora_rank"]),
        nope=int(h["qk_nope_head_dim"]), rope=int(h["qk_rope_head_dim"]),
        v=int(h["v_head_dim"]),
        adjacent=bool(h.get("rope_interleave", False)),
        theta=float(10000.0 if theta is None else theta))


def _routing(h: Dict[str, Any]) -> RoutingRule:
    """The routing rule of a description in the DeepSeek-V3 family's words:
    sigmoid scores and a correction bias (``topk_method`` noaux_tc), in
    one group. A choice limited to groups of experts, an expert layer
    every other layer, another scoring or another method have no rule
    here and are refused by name."""
    for key in ("n_group", "topk_group", "moe_layer_freq"):
        if int(h.get(key, 1)) != 1:
            raise ValueError(f"{key} {h[key]}: the routing knows one group "
                             "of experts and an expert layer every layer")
    scoring, method = h.get("scoring_func", "sigmoid"), \
        h.get("topk_method", "noaux_tc")
    if scoring != "sigmoid" or method != "noaux_tc":
        raise ValueError(f"scoring_func {scoring!r} with topk_method "
                         f"{method!r}: the family's rule here is sigmoid "
                         "scores under noaux_tc")
    return RoutingRule("sigmoid", bias=True,
                       normalised=bool(h.get("norm_topk_prob", False)),
                       scale=float(h.get("routed_scaling_factor", 1.0)))


def _phi4flash(h) -> Pattern:
    """The SambaY decoder-hybrid-decoder of arXiv:2507.06607, read at the
    PUBLISHED depth N: the layers held (all, without ``layers_held``), each
    of the kind :func:`hybrid_kind` gives its published number: Mamba-1
    state-space layers (sizes by the family's convention where the
    description is silent: ``mamba_expand`` 2, ``mamba_d_state`` 16,
    ``mamba_d_conv`` 4, ``mamba_dt_rank`` hidden / 16), gated memory units
    that gate layer N/2's scan output, differential attention
    ``sliding_window`` wide, full at N/2 + 1 and, the cross layers, on that
    layer's K and V; LayerNorms with bias (``layer_norm_eps``) before the
    branches, the feed-forward gated by ``hidden_act``, no positions
    anywhere, the head tied to the embedding. A layer that reads what a
    layer not held would hand on is refused by name."""
    of = int(h.get("n_layers", 6))
    numbers = _layers_held(h, of)
    kinds = [hybrid_kind(n, of) for n in numbers]
    # who hands on what, and the kind that reads it
    sources = {"memory": (of // 2, "gmu", "the memory"),
               "kv": (of // 2 + 1, "cross", "K and V")}
    read = {reader: name for name, (_, reader, _) in sources.items()}
    for source, reader, what in sources.values():
        if reader in kinds and source not in numbers:
            raise ValueError(
                f"layer {numbers[kinds.index(reader)]} reads {what} of "
                f"layer {source}, which is not among layers_held "
                f"{list(numbers)}")
    d_model = int(h.get("d_model", 512))
    d_inner = int(h.get("mamba_expand", 2)) * d_model
    recurrent = {
        "ssm": StateSpaceSpec(
            d_inner=d_inner, d_state=int(h.get("mamba_d_state", 16)),
            d_conv=int(h.get("mamba_d_conv", 4)),
            dt_rank=int(h.get("mamba_dt_rank") or -(-d_model // 16))),
        "gmu": MemoryUnitSpec(d_inner)}
    attention = lambda n, kind: DifferentialSpec(  # noqa: E731
        **_attention_heads(h),
        window=int(h.get("sliding_window", 512)) if kind == "window"
        else None, lambda_init=lambda_init(n), cross=kind == "cross")
    return _pattern(h, [Layer(
        n, recurrent.get(kind) or attention(n, kind),
        _gated(h), reads=(read[kind],) if kind in read else (),
        hands_on=tuple(name for name, (source, reader, _) in sources.items()
                       if n == source and reader in kinds))
        for n, kind in zip(numbers, kinds)],
        "layer", "layer_norm_eps", 1e-5, tied=True)


def hybrid_kind(layer: int, of: int) -> str:
    """The kind of published layer ``layer`` of a ``phi4flash`` model ``of``
    layers deep (``mb_per_layer`` 2: every other layer is a state-space or
    memory layer; the decoders split at ``of`` / 2)."""
    half = of // 2
    if layer % 2 == 0:
        return "ssm" if layer <= half else "gmu"
    if layer < half:
        return "window"
    if layer == half + 1:
        return "full"
    if layer >= half + 3:
        return "cross"
    raise ValueError(f"layer {layer} of {of} has no kind: the full layer is "
                     f"{half + 1}, the cross layers start at {half + 3}")


def lambda_init(layer: int) -> float:
    """The differential transformer's 0.8 - 0.6 exp(-0.3 l), at the
    PUBLISHED layer number."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _laguna(h) -> Pattern:
    """The ``laguna`` family's: the Qwen3-MoE family's pre-norm block (RMS
    norms, q/k norms over a head's width, the router read after attention)
    with, layer by layer (three lists kept whole and read up to the depth),
    the mask (``layer_types``: ``full_attention`` or ``sliding_attention``,
    ``sliding_window`` wide), the query heads
    (``num_attention_heads_per_layer``, on ``num_key_value_heads`` K/V
    heads), the rotary rule (``rope_parameters``' entry of the layer's
    type: :func:`_rotary`) and the feed-forward (``mlp_layer_types``:
    ``dense``, gated ``intermediate_size`` wide, or ``sparse``: sigmoid
    scores normalised over the chosen, times ``moe_routed_scaling_factor``,
    no correction bias, beside one shared expert
    ``shared_expert_intermediate_size`` wide); ``gating``: a sigmoid gate a
    head on attention's output; the feed-forwards gated by ``hidden_act``
    (SiLU unless named). What has no layer here is refused by its name."""
    n_layers = int(h.get("n_layers", 6))
    kv_heads = int(h.get("num_key_value_heads", 8))
    lists = {key: list(h.get(key) or ()) for key in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    for key, entries in lists.items():
        if len(entries) < n_layers:
            raise ValueError(f"{key} names {len(entries)} layers, the model "
                             f"has {n_layers}")
        lists[key] = entries[:n_layers]
    for key, known in (("layer_types", _MASKS), ("mlp_layer_types", _FEEDS)):
        unknown = sorted(set(lists[key]) - set(known))
        if unknown:
            raise ValueError(f"{key} names {unknown}; known: {sorted(known)}")
    for key in ("attention_bias", "moe_apply_router_weight_on_input"):
        if h.get(key):
            raise ValueError(f"{key} {h[key]!r}: a laguna layer has none "
                             "here")
    if "heads_held" in h:
        raise ValueError(f"heads_held {h['heads_held']!r}: a laguna layer's "
                         "head count is its own "
                         "(num_attention_heads_per_layer) and the deployment "
                         "shares no heads")
    gating = h.get("gating", False)
    if not isinstance(gating, bool):
        raise ValueError(f"gating {gating!r}: known: true (a sigmoid gate a "
                         "head) and false")
    head_dim = int(h.get("head_dim") or int(h.get("d_model", 512))
                   // int(h.get("n_heads", 8)))
    ropes = h.get("rope_parameters") or {}
    rules = {kind: _rotary(kind, ropes.get(kind) or {}, head_dim)
             for kind in set(lists["layer_types"])}
    act = {**h, "hidden_act": h.get("hidden_act", "silu")}
    feeds = {"dense": _gated(act), "sparse": _feed_forward(
        act, True, RoutingRule(
            "sigmoid", bias=False, normalised=True,
            scale=float(h.get("moe_routed_scaling_factor", 1.0))),
        int(h.get("shared_expert_intermediate_size") or 0))}
    layers = []
    for i, (kind, feed, heads) in enumerate(zip(*lists.values())):
        if int(heads) % kv_heads:
            raise ValueError(f"num_attention_heads_per_layer[{i}] {heads}: "
                             f"{kv_heads} K/V heads do not divide it")
        layers.append(Layer(i, GroupedSpec(
            heads=int(heads), kv_heads=kv_heads, head_dim=head_dim,
            window=int(h.get("sliding_window", 512)) if _MASKS[kind]
            else None, theta=rules[kind].theta, qk_norm="head",
            selection=None, rotary=rules[kind],
            gate="sigmoid" if gating else None), feeds[feed]))
    return _pattern(h, layers, "rms")


#: a ``laguna`` ``layer_types`` entry -> is the layer under the window?
_MASKS = {"full_attention": False, "sliding_attention": True}
#: its ``mlp_layer_types`` entries
_FEEDS = ("dense", "sparse")


def _rotary(kind: str, said: Dict[str, Any], head_dim: int) -> Rotary:
    """The rotary rule of one ``rope_parameters`` entry (``kind``'s), for
    heads ``head_dim`` wide: ``rope_type`` ``default`` or ``yarn`` (another
    is refused by name), the first ``partial_rotary_factor`` of a head's
    channels turned."""
    rope_type = said.get("rope_type", "default")
    if rope_type not in ("default", "yarn"):
        raise ValueError(f"rope_parameters.{kind}.rope_type {rope_type!r}: "
                         "known: 'default' and 'yarn'")
    turned = int(head_dim * float(said.get("partial_rotary_factor", 1.0)))
    yarn, factor = None, 1.0
    if rope_type == "yarn":
        yarn = (float(said["factor"]),
                int(said["original_max_position_embeddings"]),
                float(said["beta_fast"]), float(said["beta_slow"]))
        factor = float(said["attention_factor"])
    return Rotary(float(said.get("rope_theta", 10000.0)),
                  turned if turned < head_dim else None, yarn, factor)


def _nemotron_h(h) -> Pattern:
    """The ``nemotron_h`` family's: ONE sublayer a block, x + f(rmsnorm(x))
    (eps ``norm_eps``), f by the block's letter in
    ``hybrid_override_pattern``, read at the PUBLISHED numbers (all blocks,
    or ``layers_held``, a pipeline stage's): ``M`` a Mamba-2 mixer
    (``mamba_num_heads`` heads of ``mamba_head_dim``, B and C of
    ``ssm_state_size`` shared by ``n_groups`` groups, ``conv_kernel`` taps
    with a bias); ``*`` grouped attention without positions, q/k norms or
    bias; ``E`` ``n_routed_experts`` experts of two matrices
    ``moe_intermediate_size`` wide under ``mlp_hidden_act`` (a squared
    ReLU), chosen by the DeepSeek-V3 family's rule (:func:`_routing`),
    beside ``n_shared_experts`` shared ones of
    ``moe_shared_expert_intermediate_size``. An untied head. What has no
    layer here is refused by its name."""
    letters = str(h["hybrid_override_pattern"])
    unknown = sorted(set(letters) - set(_LETTERS))
    if unknown:
        raise ValueError(f"hybrid_override_pattern names {unknown}; known: "
                         f"{sorted(_LETTERS)} "
                         f"({', '.join(_LETTERS.values())})")
    numbers = _layers_held(h, len(letters))
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias"):
        if h.get(key):
            raise ValueError(f"{key} {h[key]!r}: a nemotron_h block has no "
                             "such bias here")
    if not h.get("use_conv_bias", True):
        raise ValueError("use_conv_bias false: the mixer's convolution has "
                         "its bias here")
    act = str(h.get("mlp_hidden_act", "relu2"))
    if act != "relu2" or h.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"mlp_hidden_act {act!r} with mamba_hidden_act "
                         f"{h.get('mamba_hidden_act')!r}: the family's here "
                         "are 'relu2' and 'silu'")
    heads, groups = int(h["mamba_num_heads"]), int(h.get("n_groups", 1))
    if heads % groups:
        raise ValueError(f"n_groups {groups} does not divide "
                         f"mamba_num_heads {heads}")
    if "heads_held" in h:
        raise ValueError(f"heads_held {h['heads_held']!r}: the deployment "
                         "shares no heads of a nemotron_h block")
    n_experts = int(h.get("moe_num_primary_experts", 0))
    kinds = {
        "M": (ScalarDecaySpec(
            heads=heads, head_dim=int(h["mamba_head_dim"]), groups=groups,
            state=int(h["ssm_state_size"]),
            conv=int(h.get("conv_kernel", 4))), Absent()),
        "*": (GroupedSpec(**_attention_heads(h), window=None, theta=None,
                          qk_norm=None, selection=None), Absent()),
        "E": (Absent(), RoutedSpec(
            n_experts=n_experts, top_k=int(h.get("num_experts_per_tok", 1)),
            d_ff=int(h["moe_intermediate_size"]),
            held=_held(h, "experts_held", n_experts), activation=act,
            shared_d_ff=int(h.get("n_shared_experts") or 0) * int(
                h.get("moe_shared_expert_intermediate_size", 0)),
            rule=_routing(h), router_after_mixer=True, gated=False)
            if "E" in letters else None)}
    return _pattern(h, [Layer(n, *kinds[letters[n]]) for n in numbers],
                    "rms", "norm_eps", 1e-5)


#: a ``hybrid_override_pattern`` letter -> the block's one sublayer
_LETTERS = {"M": "a Mamba-2 mixer", "*": "attention", "E": "experts"}


def _lfm2_moe(h) -> Pattern:
    """The ``lfm2_moe`` family's (LFM2, arXiv:2511.23404): the pre-norm
    block x + mix(rmsnorm(x)) then x + feed(rmsnorm(x)) (eps ``norm_eps``),
    read at the PUBLISHED numbers (all of ``layer_types``, or
    ``layers_held``, a pipeline stage's). Layer l's mixer by
    ``layer_types[l]``: ``conv`` a gated short convolution over the hidden
    width (``conv_L_cache`` taps; ``conv_bias`` true is refused, the
    published value is false), ``full_attention`` grouped attention with
    RMS norms of q and k over a head's width, then rotary positions over
    the whole head (``rope_parameters.rope_theta``, ``rope_type`` default).
    Its feed-forward gated by SiLU: ``intermediate_size`` wide for l <
    ``num_dense_layers``, else top ``num_experts_per_tok`` of
    ``num_experts`` experts ``moe_intermediate_size`` wide by sigmoid
    scores, a correction bias in the choice alone (``use_expert_bias``),
    the chosen scores over their sum + 1e-6 (``norm_topk_prob``) times
    ``routed_scaling_factor``, the router read after the mixer, no shared
    expert. The last norm (the family's ``embedding_norm``) at the output;
    the head tied to the embedding (the family's default). What has no
    layer here is refused by its name."""
    types = list(h.get("layer_types") or ())
    unknown = sorted(set(types) - set(_MIXERS))
    if unknown or not types:
        raise ValueError(f"layer_types names {unknown or 'no layer'}; "
                         f"known: {sorted(_MIXERS)}")
    numbers = _layers_held(h, len(types))
    if h.get("conv_bias"):
        raise ValueError(f"conv_bias {h['conv_bias']!r}: a gated short "
                         "convolution has no bias here")
    if "heads_held" in h:
        raise ValueError(f"heads_held {h['heads_held']!r}: the deployment "
                         "shares no heads of an lfm2_moe layer")
    dense = int(h.get("num_dense_layers", 0))
    if not 0 <= dense <= len(types):
        raise ValueError(f"num_dense_layers {dense}: the model has "
                         f"{len(types)} layers")
    rope_type = (h.get("rope_parameters") or {}).get("rope_type", "default")
    if rope_type != "default":
        raise ValueError(f"rope_parameters.rope_type {rope_type!r}: known: "
                         "'default'")
    mixers = {
        "conv": ShortConvSpec(channels=int(h.get("d_model", 512)),
                              taps=int(h.get("conv_L_cache", 3))),
        "full_attention": GroupedSpec(
            **_attention_heads(h), window=None, theta=float(_theta(h)),
            qk_norm="head", selection=None)}
    n_experts = int(h.get("moe_num_primary_experts", 0))
    routed = n_experts and RoutedSpec(
        n_experts=n_experts,
        top_k=int(h.get("moe_num_active_primary_experts", 1)),
        d_ff=_expert_width(h), held=_held(h, "experts_held", n_experts),
        activation="silu", shared_d_ff=0, rule=RoutingRule(
            "sigmoid", bias=bool(h.get("use_expert_bias", False)),
            normalised=bool(h.get("norm_topk_prob", True)),
            scale=float(h.get("routed_scaling_factor", 1.0)), eps=1e-6),
        router_after_mixer=True)
    if not routed and dense < len(types):
        raise ValueError(f"num_experts {n_experts}: the layers from "
                         f"num_dense_layers {dense} on route over experts")
    feed = GatedSpec(int(h.get("d_ff", 2048)), "silu")
    return _pattern(
        h, [Layer(n, mixers[types[n]], feed if n < dense else routed)
            for n in numbers], "rms", "norm_eps", 1e-5, tied=True)


#: an ``lfm2_moe`` ``layer_types`` entry -> the layer's mixer
_MIXERS = ("conv", "full_attention")


#: a family's reader: what its layer is, by the family and not by how a
#: key of its description is spelt
_FAMILIES = {"layouts": _layouts, "qwen3_moe": _qwen3_moe,
             "olmo_hybrid": _olmo_hybrid, "deepseek_v3": _deepseek_v3,
             "phi4flash": _phi4flash, "laguna": _laguna,
             "nemotron_h": _nemotron_h, "lfm2_moe": _lfm2_moe}


def describe_pattern(hparams: Dict[str, Any], route: str, tokens: int,
                     seq_len: Optional[int] = None,
                     mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """What ``trial.setup``'s span says of a description with a layer
    pattern ({} without one), for steps of ``tokens`` tokens in rows of
    ``seq_len`` on attention route ``route`` under ``mesh``: the route the
    embedding's gradient takes (ops/embed.embed_gradient_route: ``"sorted"``
    or ``"take"``), the table's held rows and width, the tokens a step
    looks up and whether the head reads the same table; under
    ``attention_layers`` what each kind of layer's mixer spec says of itself
    (``Pattern.by_kind``'s order), and what each kind of feed-forward's
    says (the expert layers', under ``moe``)."""
    h = _own_names(hparams)
    p = pattern_of(h)
    if p is None:
        return {}
    step = Step(route, mesh, tokens, seq_len, int(h.get("d_model", 512)),
                int(h.get("d_ff", 2048)))
    sources = {name: layer.number for layer in p.layers
               for name in layer.hands_on}
    out = {"attention_layers": {
               kind: layers[0].mixer.describe(step, layers, sources)
               for kind, layers in p.by_kind()},
           "embed": {"gradient": embed_gradient_route(mesh),
                     "rows": p.vocab_held[1], "width": step.d_model,
                     "tokens": tokens, "tied": p.tied}}
    feeds: Dict[str, list] = {}
    for layer in p.layers:
        feeds.setdefault(layer.ffn.kind, []).append(layer)
    fed = sum(not isinstance(layer.ffn, Absent) for layer in p.layers)
    for layers in feeds.values():
        out.update(layers[0].ffn.describe(step, layers, fed))
    return out
