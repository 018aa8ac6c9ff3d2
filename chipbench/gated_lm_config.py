"""From the configuration file of a decoder whose full and window layers
differ in head count and rotary rule, with a gate on attention's output and
sigmoid routing beside a shared expert (the ``laguna`` family's keys:
``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters.{full_attention, sliding_attention}``, ``gating``,
``shared_expert_intermediate_size``, ``moe_routed_scaling_factor`` ...) to
the two descriptions the benchmark needs, as the five other
``*_lm_config.py`` do for their families: the program's
(``models/lm.py::make_lm``'s hyperparameters) and the plain reference's
(``reference/gated_lm.py``'s ``cfg``). Dicts in, dicts out: nothing of the
program or of jax is imported. ``python -m chipbench.gated_lm_config FILE``
prints the first as JSON, which is what ``examples/lm_causal.py --model``
reads.

The file keeps the published config's keys at its top level, with the three
cut ones (``reduced``) at the size held here and the three lists whole (40
entries, read up to the depth); ``script_args.share`` says what the chip
holds of what is routed over, and ``script_args.model`` overrides widths
for a rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "attention_bias", "gating", "head_dim", "hidden_size",
    "intermediate_size", "layer_types", "mlp_layer_types", "model_type",
    "moe_apply_router_weight_on_input", "moe_intermediate_size",
    "moe_routed_scaling_factor", "num_attention_heads",
    "num_attention_heads_per_layer", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rms_norm_eps",
    "rope_parameters", "shared_expert_intermediate_size", "sliding_window",
    "tie_word_embeddings", "vocab_size")
#: a ``layer_types`` entry -> the reference's word for the layer's kind
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["num_experts"] != share["experts_held"][1] \
            or model["vocab_size"] != share["vocab_held"][1]:
        raise ValueError("the experts and vocabulary rows held disagree "
                         "with script_args.share")
    for key, must in (("model_type", "laguna"), ("attention_bias", False),
                      ("moe_apply_router_weight_on_input", False),
                      ("tie_word_embeddings", False), ("gating", True)):
        if model[key] != must:
            raise ValueError(f"{key} {model[key]!r}: program and reference "
                             f"follow {must!r} alone")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names, the share,
    ``remat`` and the optimizer's hyperparameters."""
    a = config["script_args"]
    desc = _model(config)
    desc.update(config["hparams"])
    desc.update(num_experts=a["share"]["experts_routed_over"],
                experts_held=a["share"]["experts_held"],
                vocab_held=a["share"]["vocab_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def _rule(said: dict, head_dim: int) -> dict:
    """``reference/gated_lm.py``'s rotary rule of one ``rope_parameters``
    entry; a ``rope_type`` the reference does not follow is refused by
    name."""
    if said["rope_type"] not in ("default", "yarn"):
        raise ValueError(f"rope_type {said['rope_type']!r}: the reference "
                         "follows 'default' and 'yarn' alone")
    yarn = said["rope_type"] == "yarn"
    return {
        "theta": float(said["rope_theta"]),
        "turned": int(head_dim * said["partial_rotary_factor"]),
        "yarn": [float(said["factor"]),
                 int(said["original_max_position_embeddings"]),
                 float(said["beta_fast"]), float(said["beta_slow"])]
        if yarn else None,
        "factor": float(said["attention_factor"]) if yarn else 1.0}


def reference_cfg(config: dict) -> dict:
    """``reference/gated_lm.py``'s ``cfg`` (``flops_lm.experts_pass`` reads
    the keys it shares with ``lm_config.reference_cfg``)."""
    m, share = _model(config), config["script_args"]["share"]
    depth = m["num_hidden_layers"]
    return {
        "d_model": m["hidden_size"], "head_dim": m["head_dim"],
        "n_kv_heads": m["num_key_value_heads"],
        "window": m["sliding_window"], "rms_eps": m["rms_norm_eps"],
        "layers": [{"kind": KINDS[kind], "heads": heads, "ffn": feed}
                   for kind, heads, feed in zip(
                       m["layer_types"][:depth],
                       m["num_attention_heads_per_layer"][:depth],
                       m["mlp_layer_types"][:depth])],
        "rope": {KINDS[kind]: _rule(said, m["head_dim"])
                 for kind, said in m["rope_parameters"].items()
                 if kind in KINDS},
        "gate": "sigmoid",
        "d_ff": m["intermediate_size"],
        "n_experts": share["experts_routed_over"],
        "top_k": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "shared_d_ff": m["shared_expert_intermediate_size"],
        "normalised": True, "scale": float(m["moe_routed_scaling_factor"]),
        "activation": "silu",
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
