"""From the configuration file of a decoder-hybrid-decoder with state-space
layers (the ``phi4flash`` family's keys: ``mb_per_layer``,
``sliding_window``, ``layer_norm_eps`` ...) to the two descriptions the
benchmark needs, as the four other ``*_lm_config.py`` do for their
families: the program's (``models/lm.py::make_lm``'s hyperparameters) and
the plain reference's (``reference/ssm_lm.py``'s ``cfg``). Dicts in, dicts
out: nothing of the program or of jax is imported. ``python -m
chipbench.ssm_lm_config FILE`` prints the first as JSON, which is what
``examples/lm_causal.py --model`` reads.

The file keeps the published config's keys at its top level, with the two
cut ones (``reduced``) at the size held here: ``num_hidden_layers`` counts
the layers held, ``vocab_size`` the rows. ``script_args.share`` says which
published layers those are (``layers_held`` of ``layers_of``: kinds and
lambda_init are read at the published numbers) and which rows;
``assumed.mamba`` holds Mamba-1's sizes, which the published config leaves
to the family's convention; ``script_args.model`` overrides widths for a
rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "hidden_act", "hidden_size", "intermediate_size", "layer_norm_eps",
    "mb_per_layer", "mlp_bias", "lm_head_bias", "model_type",
    "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
    "sliding_window", "tie_word_embeddings", "vocab_size")
MAMBA_KEYS = ("mamba_expand", "mamba_d_state", "mamba_d_conv",
              "mamba_dt_rank")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update({k: config["assumed"]["mamba"][k] for k in MAMBA_KEYS})
    model.update(a.get("model", {}))
    share = a["share"]
    if model["num_hidden_layers"] != len(share["layers_held"]) \
            or model["vocab_size"] != share["vocab_held"][1]:
        raise ValueError("the layers and vocabulary rows held disagree "
                         "with script_args.share")
    for key, must in (("model_type", "phi4flash"), ("mb_per_layer", 2),
                      ("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False), ("hidden_act", "silu")):
        if model[key] != must:
            raise ValueError(f"{key} {model[key]!r}: program and reference "
                             f"follow {must!r} alone")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names at the
    published depth, the layers and rows held, ``remat`` and the
    optimizer's hyperparameters."""
    a = config["script_args"]
    desc = _model(config)
    desc.update(config["hparams"])
    desc.update(num_hidden_layers=a["share"]["layers_of"],
                layers_held=a["share"]["layers_held"],
                vocab_held=a["share"]["vocab_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/ssm_lm.py``'s ``cfg``."""
    m, share = _model(config), config["script_args"]["share"]
    return {
        "d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"],
        "head_dim": m["hidden_size"] // m["num_attention_heads"],
        "window": m["sliding_window"], "eps": m["layer_norm_eps"],
        "layers": list(share["layers_held"]), "of": share["layers_of"],
        "d_inner": m["mamba_expand"] * m["hidden_size"],
        "d_state": m["mamba_d_state"], "d_conv": m["mamba_d_conv"],
        "dt_rank": m["mamba_dt_rank"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
