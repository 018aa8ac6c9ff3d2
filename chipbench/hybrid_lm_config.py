"""From the configuration file of a hybrid linear-attention decoder (the
Olmo hybrid family's keys: ``layer_types``, ``linear_*``) to the two
descriptions the benchmark needs, as ``lm_config.py`` and
``sparse_lm_config.py`` do for their families: the program's
(``models/lm.py::make_lm``'s hyperparameters) and the plain reference's
(``reference/hybrid_lm.py``'s ``cfg``). Dicts in, dicts out: nothing of the
program or of jax is imported. ``python -m chipbench.hybrid_lm_config FILE``
prints the first as JSON, which is what ``examples/lm_causal.py --model``
reads.

The file keeps the published config's keys at its top level, with the cut
ones (``reduced``) at the size held here: the four head counts say the
heads this chip holds, ``vocab_size`` its rows; ``layer_types`` stays whole
and is read up to ``num_hidden_layers``. ``script_args.share`` says what is
held of what (``heads_held`` of ``heads_of``), and ``script_args.model``
overrides widths for a rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "hidden_act", "hidden_size", "intermediate_size", "layer_types",
    "linear_allow_neg_eigval", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_num_key_heads", "linear_num_value_heads",
    "linear_value_head_dim", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_parameters", "vocab_size")
#: the four counts of heads: all cut by the one share
HEAD_KEYS = ("num_attention_heads", "num_key_value_heads",
             "linear_num_key_heads", "linear_num_value_heads")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if any(model[k] != share["heads_held"][1] for k in HEAD_KEYS) \
            or model["vocab_size"] != share["vocab_held"][1]:
        raise ValueError("the heads and vocabulary rows held disagree with "
                         "script_args.share")
    if model["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("this family's full layers have no positions")
    return model


def description(config: dict) -> dict:
    """What ``make_lm`` / ``LMTrial`` take: the published names with the
    layer's own head counts, the share, ``remat`` and the optimizer's
    hyperparameters."""
    a = config["script_args"]
    desc = _model(config)
    desc.update(config["hparams"])
    desc.update({k: a["share"]["heads_of"] for k in HEAD_KEYS})
    desc.update(heads_held=a["share"]["heads_held"],
                vocab_held=a["share"]["vocab_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/hybrid_lm.py``'s ``cfg``: the heads are the held ones."""
    m, share = _model(config), config["script_args"]["share"]
    n = m["num_hidden_layers"]
    return {
        "d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
        "n_heads": m["num_attention_heads"],
        "head_dim": m["hidden_size"] // share["heads_of"],
        "linear": [t == "linear_attention" for t in m["layer_types"][:n]],
        "linear_heads": m["linear_num_value_heads"],
        "key_dim": m["linear_key_head_dim"],
        "value_dim": m["linear_value_head_dim"],
        "conv": m["linear_conv_kernel_dim"],
        "neg_eigval": m["linear_allow_neg_eigval"],
        "activation": m["hidden_act"], "rms_eps": m["rms_norm_eps"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
