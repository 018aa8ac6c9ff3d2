"""From the configuration file of a decoder of one sublayer a block (the
``nemotron_h`` family's keys: ``hybrid_override_pattern``, ``mamba_num_heads``,
``mamba_head_dim``, ``n_groups``, ``ssm_state_size``, ``conv_kernel``,
``n_routed_experts``, ``moe_shared_expert_intermediate_size``,
``mlp_hidden_act`` ...) to the two descriptions the benchmark needs, as the
six other ``*_lm_config.py`` do for their families: the program's
(``models/lm.py::make_lm``'s hyperparameters) and the plain reference's
(``reference/ssd_lm.py``'s ``cfg``). Dicts in, dicts out: nothing of the
program or of jax is imported. ``python -m chipbench.ssd_lm_config FILE``
prints the first as JSON, which is what ``examples/lm_causal.py --model``
reads.

The file keeps the published config's keys at its top level, with the three
cut ones (``reduced``) at the size held here and the pattern whole (52
letters, read at the published numbers ``script_args.share.layers_held``
names); ``script_args.share`` says what the chip holds of what is routed
over, and ``script_args.model`` overrides widths for a rehearsal (and for
nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim",
    "hidden_size", "hybrid_override_pattern", "intermediate_size",
    "mamba_head_dim", "mamba_hidden_act", "mamba_num_heads",
    "mamba_proj_bias", "mlp_bias", "mlp_hidden_act", "model_type",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_group",
    "n_groups", "n_routed_experts", "n_shared_experts", "norm_eps",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rope_theta",
    "routed_scaling_factor", "ssm_state_size", "tie_word_embeddings",
    "time_step_floor", "time_step_max", "time_step_min", "topk_group",
    "use_bias", "use_conv_bias", "vocab_size")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["n_routed_experts"] != share["experts_held"][1] \
            or model["vocab_size"] != share["vocab_held"][1] \
            or model["num_hidden_layers"] != len(share["layers_held"]):
        raise ValueError("the experts, vocabulary rows and blocks held "
                         "disagree with script_args.share")
    for key, must in (("model_type", "nemotron_h"), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mlp_bias", False),
                      ("use_bias", False), ("use_conv_bias", True),
                      ("tie_word_embeddings", False), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("mlp_hidden_act", "relu2"),
                      ("mamba_hidden_act", "silu"), ("n_shared_experts", 1)):
        if model[key] != must:
            raise ValueError(f"{key} {model[key]!r}: program and reference "
                             f"follow {must!r} alone")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names, the share,
    ``remat`` and the optimizer's hyperparameters."""
    a = config["script_args"]
    share = a["share"]
    desc = _model(config)
    desc.update(config["hparams"])
    desc.update(n_routed_experts=share["experts_routed_over"],
                experts_held=share["experts_held"],
                vocab_held=share["vocab_held"],
                layers_held=share["layers_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/ssd_lm.py``'s ``cfg`` (``flops_ssd_lm.py`` reads it
    too)."""
    m, share = _model(config), config["script_args"]["share"]
    numbers = list(share["layers_held"])
    return {
        "d_model": m["hidden_size"], "rms_eps": m["norm_eps"],
        "numbers": numbers,
        "letters": "".join(m["hybrid_override_pattern"][n] for n in numbers),
        "ssd_heads": m["mamba_num_heads"], "ssd_head_dim": m["mamba_head_dim"],
        "ssd_groups": m["n_groups"], "ssd_state": m["ssm_state_size"],
        "ssd_conv": m["conv_kernel"], "chunk": m["chunk_size"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "rope_theta": float(m["rope_theta"]),
        "n_experts": share["experts_routed_over"],
        "top_k": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "shared_d_ff": m["moe_shared_expert_intermediate_size"],
        "normalised": True, "scale": float(m["routed_scaling_factor"]),
        "time_step": [m["time_step_min"], m["time_step_max"],
                      m["time_step_floor"]],
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
