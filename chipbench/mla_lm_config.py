"""From the configuration file of a latent-attention MoE decoder (the
DeepSeek-V3 family's keys: ``kv_lora_rank``, ``qk_*_head_dim``,
``n_routed_experts``, ``first_k_dense_replace`` ...) to the two
descriptions the benchmark needs, as ``lm_config.py``,
``sparse_lm_config.py`` and ``hybrid_lm_config.py`` do for their families:
the program's (``models/lm.py::make_lm``'s hyperparameters) and the plain
reference's (``reference/mla_lm.py``'s ``cfg``). Dicts in, dicts out:
nothing of the program or of jax is imported. ``python -m
chipbench.mla_lm_config FILE`` prints the first as JSON, which is what
``examples/lm_causal.py --model`` reads.

The file keeps the published config's keys at its top level, with the
three cut ones (``reduced``) at the size held here; ``script_args.share``
says what the chip holds of what is routed over, and ``script_args.model``
overrides widths for a rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_interleave", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "scoring_func", "topk_group",
    "topk_method", "v_head_dim", "vocab_size")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["n_routed_experts"] != share["experts_held"][1] \
            or model["vocab_size"] != share["vocab_held"][1]:
        raise ValueError("the experts and vocabulary rows held disagree "
                         "with script_args.share")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names, the share,
    ``remat`` and the optimizer's hyperparameters."""
    a = config["script_args"]
    desc = _model(config)
    desc.pop("qk_head_dim", None)
    desc.update(config["hparams"])
    desc.update(n_routed_experts=a["share"]["experts_routed_over"],
                experts_held=a["share"]["experts_held"],
                vocab_held=a["share"]["vocab_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/mla_lm.py``'s ``cfg`` (``flops_lm.experts_pass`` reads
    the keys it shares with ``lm_config.reference_cfg``). What the
    reference does not compute is refused here by name, as the program
    refuses it."""
    m, share = _model(config), config["script_args"]["share"]
    for key, must in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("rope_interleave", True)):
        if m[key] != must:
            raise ValueError(f"{key} {m[key]!r}: the reference follows "
                             f"{must!r} alone")
    return {
        "d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
        "n_layers": m["num_hidden_layers"],
        "rank": m["kv_lora_rank"], "nope": m["qk_nope_head_dim"],
        "rope": m["qk_rope_head_dim"], "v_dim": m["v_head_dim"],
        "rope_theta": float(m["rope_theta"]), "rms_eps": m["rms_norm_eps"],
        "dense_layers": min(m["first_k_dense_replace"],
                            m["num_hidden_layers"]),
        "d_ff": m["intermediate_size"],
        "n_experts": share["experts_routed_over"],
        "top_k": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "shared_d_ff": m["n_shared_experts"] * m["moe_intermediate_size"],
        "normalised": bool(m["norm_topk_prob"]),
        "scale": float(m["routed_scaling_factor"]),
        "activation": m["hidden_act"],
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
