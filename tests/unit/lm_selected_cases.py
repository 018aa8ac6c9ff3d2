"""What the files of the selected-attention decoder's tests share
(test_lm_selected.py, test_lm_selected_index.py,
test_lm_selected_reference.py): the small description, its sizes, the
gradient leaves the comparisons name."""

import numpy as np

D, H, KV, HD, F, E, TOPK, V, S = 32, 4, 2, 16, 24, 16, 3, 64, 40
IH, IK, KEYS = 4, 8, 12


def description(layers=1, held=(0, E), **over):
    h = dict(hidden_size=D, num_attention_heads=H, num_key_value_heads=KV,
             head_dim=HD, num_hidden_layers=layers, vocab_size=V,
             rope_theta=1e7, rms_norm_eps=1e-6, hidden_act="silu",
             num_experts=E, num_experts_per_tok=TOPK,
             moe_intermediate_size=F, experts_held=held,
             sa_config={"indexer_head_dim": IK, "indexer_num_heads": IH,
                        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                        "q_chunk_size": 512, "topk": KEYS})
    h.update(over)
    return h


LEAVES = ["embed/embedding", "head/embedding", "norm_f/scale",
          "h0/norm_in/scale", "h0/norm_post/scale", "h0/router/kernel",
          "h0/attn/q/kernel", "h0/attn/k/kernel", "h0/attn/v/kernel",
          "h0/attn/out/kernel", "h0/attn/q_norm/scale",
          "h0/attn/k_norm/scale", "h0/experts/gate/e00",
          "h0/experts/up/e05", "h0/experts/down/e15"]


def leaf(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return np.asarray(tree, np.float32)
