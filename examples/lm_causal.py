#!/usr/bin/env python
"""Decoder-only causal LM trial — the long-context zoo entry.

    python -m metaopt_tpu hunt -n lm --max-trials 20 \
        --config examples/tpe.yaml \
        examples/lm_causal.py \
        --lr~'loguniform(1e-4, 1e-1)' \
        --dropout~'uniform(0.0, 0.3)' \
        --n-layers~'uniform(1, 4, discrete=True)'

``--sp 2`` shards the sequence axis (ring attention over ICI;
METAOPT_TPU_SP_IMPL=ulysses for the all-to-all variant) — the
decoder-only model is where long-context sequence parallelism earns
its keep. Which attention a step takes is ops/attention.attention_route's
one rule, from the mesh, the backend and the dropout rate; a model with a
layer pattern (``--model``) has no sequence-parallel route.

``--model FILE`` names the model by a description: a JSON object of
``make_lm``'s hyperparameters, the published names included
(``hidden_size``, ``num_key_value_heads``, ``rope_layout``,
``sliding_window_layout``, ``moe_num_primary_experts`` ...) and the share
a chip holds (``experts_held``, ``vocab_held``). The sweep then searches
the optimizer's hyperparameters over that model. The benchmark prints the
description of a configuration of its own, SmallThinker-21BA3B's share of
one chip for example:

    python -m chipbench.lm_config \
        chipbench/configs/smallthinker-21b-a3b-ep4.json > st.json
    python -m metaopt_tpu hunt -n st --n-chips 1 --max-trials 8 \
        examples/lm_causal.py --model=st.json \
        --seq-len=8192 --batch-size=1 --n-train=64 --steps=20 \
        --lr~'loguniform(1e-5, 1e-3)'

A description in the Qwen3-MoE family's words (``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``hidden_act``) with an
``sa_config`` names a decoder whose attention runs over the keys an indexer
selects (``python -m chipbench.sparse_lm_config
chipbench/configs/keye-vl2-30b-a3b-ep8.json`` prints Keye-VL-2.0-30B-A3B's
share of one chip; run it at ``--seq-len=16384``). One with ``layer_types``
(the Olmo hybrid family's) has linear-attention layers beside full ones.
One in the DeepSeek-V3 family's words (``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``n_routed_experts``, ``n_shared_experts``, ``first_k_dense_replace``,
``scoring_func``, ``topk_method``, ``routed_scaling_factor``) names the fifth
kind of layer, *latent* attention, with a leading dense layer, sigmoid
routing and shared experts (``python -m chipbench.mla_lm_config
chipbench/configs/kanana-2-30b-a3b-ep8.json`` prints
kanana-2-30b-a3b-instruct-2601's share of one chip; ``--seq-len=16384``).
One with ``model_type`` ``phi4flash`` names a decoder-hybrid-decoder:
state-space layers (Mamba-1's selective scan), differential attention
under a window and without, gated memory units and cross attention on an
earlier layer's K and V, the layers a chip holds listed by their published
numbers in ``layers_held`` (``python -m chipbench.ssm_lm_config
chipbench/configs/phi-4-mini-flash-vp8.json`` prints
Phi-4-mini-flash-reasoning's share of one chip; ``--seq-len=8192``).
One with ``model_type`` ``laguna`` names grouped layers that differ layer
by layer (``layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters``' entry a type: 48 heads under the causal mask and YaRN
on half a head, 64 under a window of 512 and the plain rule), a sigmoid
gate a head on attention's output (``gating``) and, by
``mlp_layer_types``, a dense feed-forward or sigmoid routing beside a
shared expert (``python -m chipbench.gated_lm_config
chipbench/configs/laguna-xs2-33b-a3b-ep8.json`` prints Laguna-XS.2's share
of one chip; ``--seq-len=8192``).
One with ``hybrid_override_pattern`` (``model_type`` ``nemotron_h``) names
blocks of ONE sublayer each, by the pattern's letter at the block's
published number (``layers_held``): ``M`` a Mamba-2 mixer
(``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``, ``ssm_state_size``,
``conv_kernel``), ``*`` grouped attention without positions, ``E`` experts
of two matrices under a squared ReLU beside a shared one (``python -m
chipbench.ssd_lm_config chipbench/configs/nemotron-3-nano-30b-a3b-ep16.json``
prints NVIDIA-Nemotron-3-Nano-30B-A3B's share of one chip;
``--seq-len=8192``).
One with ``model_type`` ``lfm2_moe`` names, layer by layer
(``layer_types`` at the published numbers, ``layers_held``), a gated short
convolution (``conv``: ``conv_L_cache`` taps between two gates, no state
beyond two tokens) or grouped attention with q/k norms and rotary positions
(``full_attention``); ``num_dense_layers`` leading SwiGLU layers, then
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts by sigmoid scores
with a correction bias (``use_expert_bias``), a tied head (``python -m
chipbench.conv_lm_config chipbench/configs/lfm2-24b-a2b-ep8.json`` prints
LFM2-24B-A2B's share of one chip; ``--seq-len=8192``).
"""

import argparse
import json

from metaopt_tpu import client
from metaopt_tpu.client import report_results


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--lr", type=float, required=True)
    p.add_argument("--dropout", type=float, default=0.1)
    p.add_argument("--n-layers", dest="n_layers", type=int, default=2)
    p.add_argument("--d-model", dest="d_model", type=int, default=128)
    p.add_argument("--d-ff", dest="d_ff", type=int, default=512)
    p.add_argument("--seq-len", dest="seq_len", type=int, default=64)
    p.add_argument("--steps", type=int, default=80)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--n-experts", dest="n_experts", type=int, default=0)
    p.add_argument("--model", help="a JSON description of the model "
                   "(make_lm's hyperparameters) in place of the widths "
                   "above; a layer is linear, latent (the DeepSeek-V3 "
                   "family's words: kv_lora_rank ...), selected, window or "
                   "global, or (model_type phi4flash) state-space, a "
                   "memory unit or differential attention, by what the "
                   "description says")
    p.add_argument("--batch-size", dest="batch_size", type=int, default=32)
    p.add_argument("--n-train", dest="n_train", type=int, default=2048)
    p.add_argument("--warmup", type=int, default=10)
    a = p.parse_args()

    from metaopt_tpu.models.lm import train_lm

    kw = {}
    if client.IS_ORCHESTRATED:
        # orbax trial checkpoints: PBT handoff / suspended-trial resume
        own, parent = client.checkpoint_paths()
        kw = {"save_dir": own, "restore_dir": parent or own}
    if a.model:
        with open(a.model) as f:
            hparams = dict(json.load(f), lr=a.lr, warmup=a.warmup)
    else:
        hparams = {"lr": a.lr, "dropout": a.dropout, "d_model": a.d_model,
                   "n_layers": a.n_layers, "d_ff": a.d_ff,
                   "n_heads": max(1, a.d_model // 64),
                   "n_experts": a.n_experts, "warmup": a.warmup}
    # under ``hunt --profile-dir D`` the trial's device trace lands beside its
    # spans, and ``python -m metaopt_tpu.utils.trace D`` prints its step by
    # layer and the compiler's operations in it; a no-op otherwise
    with client.profiled():
        loss = train_lm(
            hparams, tp=a.tp, sp=a.sp, ep=a.ep, seq_len=a.seq_len,
            steps=a.steps, batch_size=a.batch_size, n_train=a.n_train,
            **kw,
        )
    report_results([{"name": "loss", "type": "objective", "value": loss}])


if __name__ == "__main__":
    main()
