"""From the configuration file of a decoder whose mixers are gated short
convolutions beside grouped attention (the ``lfm2_moe`` family's keys:
``layer_types``, ``conv_L_cache``, ``conv_bias``, ``num_dense_layers``,
``num_experts``, ``use_expert_bias``, ``norm_eps``, ``rope_parameters`` ...)
to the two descriptions the benchmark needs, as the seven other
``*_lm_config.py`` do for their families: the program's
(``models/lm.py::make_lm``'s hyperparameters) and the plain reference's
(``reference/conv_lm.py``'s ``cfg``). Dicts in, dicts out: nothing of the
program or of jax is imported. ``python -m chipbench.conv_lm_config FILE``
prints the first as JSON, which is what ``examples/lm_causal.py --model``
reads.

The file keeps the published config's keys at its top level, with the three
cut ones (``reduced``) at the size held here and ``layer_types`` whole (40
entries, read at the published numbers ``script_args.share.layers_held``
names); ``script_args.share`` says what the chip holds of what is routed
over, and ``script_args.model`` overrides widths for a rehearsal (and for
nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
    "layer_types", "model_type", "moe_intermediate_size", "norm_eps",
    "norm_topk_prob", "num_attention_heads", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rope_parameters", "routed_scaling_factor",
    "use_expert_bias", "vocab_size")
#: the normalisation's epsilon of the family's routing (``assumed`` in the
#: configuration's file): the program's reader has it written in, the
#: reference takes it from here
ROUTING_EPS = 1e-6


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["num_experts"] != share["experts_held"][1] \
            or model["vocab_size"] != share["vocab_held"][1] \
            or model["num_hidden_layers"] != len(share["layers_held"]):
        raise ValueError("the experts, vocabulary rows and layers held "
                         "disagree with script_args.share")
    for key, must in (("model_type", "lfm2_moe"), ("conv_bias", False),
                      ("norm_topk_prob", True)):
        if model[key] != must:
            raise ValueError(f"{key} {model[key]!r}: program and reference "
                             f"follow {must!r} alone")
    if model["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("rope_parameters.rope_type: program and reference "
                         "follow 'default' alone")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names, the share,
    ``remat`` and the optimizer's hyperparameters."""
    a = config["script_args"]
    share = a["share"]
    desc = _model(config)
    desc.update(config["hparams"])
    desc.update(num_experts=share["experts_routed_over"],
                experts_held=share["experts_held"],
                vocab_held=share["vocab_held"],
                layers_held=share["layers_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/conv_lm.py``'s ``cfg`` (``flops_conv_lm.py`` reads it
    too)."""
    m, share = _model(config), config["script_args"]["share"]
    numbers = list(share["layers_held"])
    return {
        "d_model": m["hidden_size"], "rms_eps": m["norm_eps"],
        "numbers": numbers,
        "kinds": [m["layer_types"][n] for n in numbers],
        "dense_layers": m["num_dense_layers"], "d_ff": m["intermediate_size"],
        "taps": m["conv_L_cache"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"],
        "head_dim": m["hidden_size"] // m["num_attention_heads"],
        "rope_theta": float(m["rope_parameters"]["rope_theta"]),
        "n_experts": share["experts_routed_over"],
        "top_k": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "normalised": True, "scale": float(m["routed_scaling_factor"]),
        "routing_eps": ROUTING_EPS, "use_bias": bool(m["use_expert_bias"]),
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
