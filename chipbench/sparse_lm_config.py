"""From the configuration file of a decoder whose attention runs over
selected keys (the Qwen3-MoE family's keys plus ``sa_config``) to the two
descriptions the benchmark needs, as ``lm_config.py`` does for the pattern
decoders with a window: the program's (``models/lm.py::make_lm``'s
hyperparameters) and the plain reference's (``reference/sparse_lm.py``'s
``cfg``). Dicts in, dicts out: nothing of the program or of jax is
imported. ``python -m chipbench.sparse_lm_config FILE`` prints the first as
JSON, which is what ``examples/lm_causal.py --model`` reads.

The file keeps the published config's keys at its top level, with the
three cut ones (``reduced``) at the size held here; ``script_args.share``
says what the chip holds of what is routed over, and ``script_args.model``
overrides widths for a rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "head_dim", "hidden_act", "hidden_size", "moe_intermediate_size",
    "num_attention_heads", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "rms_norm_eps", "rope_theta",
    "sa_config", "vocab_size")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["num_experts"] != share["experts_held"][1] \
            or model["vocab_size"] != share["vocab_held"][1]:
        raise ValueError("the experts and vocabulary rows held disagree "
                         "with script_args.share")
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


def reference_cfg(config: dict) -> dict:
    """``reference/sparse_lm.py``'s ``cfg`` (``flops_lm.py``'s functions
    read the keys it shares with ``lm_config.reference_cfg``)."""
    m, share = _model(config), config["script_args"]["share"]
    sa = m["sa_config"]
    return {
        "d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "n_layers": m["num_hidden_layers"],
        "rope_theta": float(m["rope_theta"]), "rms_eps": m["rms_norm_eps"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "top_keys": sa["topk"],
        "n_experts": share["experts_routed_over"],
        "top_k": m["num_experts_per_tok"],
        "expert_d_ff": m["moe_intermediate_size"],
        "activation": m["hidden_act"],
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
