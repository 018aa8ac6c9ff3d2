"""From a pattern decoder's configuration file to the two descriptions the
benchmark needs: the program's (``models/lm.py::make_lm``'s
hyperparameters) and the plain reference's (``reference/lm.py``'s ``cfg``).
Dicts in, dicts out: nothing of the program or of jax is imported.
``python -m chipbench.lm_config FILE`` prints the first as JSON, which is
what ``examples/lm_causal.py --model`` reads: the program knows nothing of
this file's layout.

The file keeps the published config's keys at its top level, with the
three cut ones (``reduced``) at the size held here; ``script_args.share``
says what the chip holds of what is routed over, and ``script_args.model``
overrides widths for a rehearsal (and for nothing else).
"""

from __future__ import annotations

PUBLISHED_KEYS = (
    "head_dim", "hidden_size", "moe_ffn_hidden_size",
    "moe_num_active_primary_experts", "moe_num_primary_experts",
    "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
    "rms_norm_eps", "rope_layout", "rope_theta", "sliding_window_layout",
    "sliding_window_size", "vocab_size")


def _model(config: dict) -> dict:
    a = config["script_args"]
    model = {k: config[k] for k in PUBLISHED_KEYS}
    model.update(a.get("model", {}))
    share = a["share"]
    if model["moe_num_primary_experts"] != share["experts_held"][1] \
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
    desc.update(moe_num_primary_experts=a["share"]["experts_routed_over"],
                experts_held=a["share"]["experts_held"],
                vocab_held=a["share"]["vocab_held"], remat=a["remat"],
                dropout=0.0)
    return desc


def reference_cfg(config: dict) -> dict:
    """``reference/lm.py``'s ``cfg``."""
    m, share = _model(config), config["script_args"]["share"]
    n = m["num_hidden_layers"]
    return {
        "d_model": m["hidden_size"], "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "layers": [(bool(s), bool(r)) for s, r in
                   zip(m["sliding_window_layout"][:n], m["rope_layout"][:n])],
        "window": m["sliding_window_size"], "rope_theta": float(m["rope_theta"]),
        "rms_eps": m["rms_norm_eps"],
        "n_experts": share["experts_routed_over"],
        "top_k": m["moe_num_active_primary_experts"],
        "expert_d_ff": m["moe_ffn_hidden_size"],
        "experts_held": share["experts_held"],
        "vocab_held": share["vocab_held"],
    }


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as _f:
        print(json.dumps(description(json.load(_f))))
