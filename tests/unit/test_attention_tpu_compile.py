"""The Pallas attention kernels compile through Mosaic for a TPU v5e that
is described, not attached: what interpret mode cannot show (tiling rules,
unaligned slices, VMEM limits), at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and under several test workers every
worker imports this file while one runs it. Keep these tests in this one
file.
"""

import jax
import jax.numpy as jnp
import pytest

from metaopt_tpu.ops.attention import CausalMask, flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (batch, Sq, Sk, heads, head width): the benchmark cell's attention, its
# long twin, a sequence of several tiles, the zoo's default trial length
# (one tile under a lane tile), lengths that pad, cross attention with a
# short query side, and one head of a tp=8 shard
SHAPES = [(64, 256, 256, 8, 64), (64, 512, 512, 8, 64), (4, 1024, 1024, 8, 64),
          (32, 64, 64, 8, 64), (4, 200, 77, 8, 64), (4, 40, 300, 8, 64),
          (4, 256, 256, 1, 64)]


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "unmasked"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_forward_and_backward_compile_for_a_v5e(one_chip, shape, masked):
    b, sq, sk, h, d = shape
    q = jax.ShapeDtypeStruct((b, sq, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, sk, h, d), jnp.bfloat16, sharding=one_chip)
    args = [q, kv, kv]
    if masked:
        args.append(jax.ShapeDtypeStruct((b, sq, sk), jnp.bool_,
                                         sharding=one_chip))

    def loss(q, k, v, mask=None):
        out = flash_attention(q, k, v, mask, impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # fwd, bwd


# (length, window, query heads, K/V heads, head width): SmallThinker's global
# and window layers at the benchmark cell's 8192 tokens and at the model's
# 16384, a length that pads, 64-wide heads, and the gated mixed-window
# cell's two kinds at its 8192 tokens: 64 query heads under the window of
# 512 and 48 under the causal mask, on 8 K/V heads (groups of 8 and of 6)
STRUCTURAL = [(8192, None, 28, 4, 128), (8192, 4096, 28, 4, 128),
              (16384, 4096, 28, 4, 128), (16384, None, 28, 4, 128),
              (1000, 300, 8, 2, 128), (2048, 512, 8, 8, 64),
              (8192, 512, 64, 8, 128), (8192, None, 48, 8, 128)]


@pytest.mark.parametrize("case", STRUCTURAL,
                         ids=lambda c: "x".join(map(str, c)))
def test_structural_masks_and_grouped_heads_compile_for_a_v5e(one_chip, case):
    """VMEM stays bounded: a program holds one head of the other sequence,
    2 MiB at 8192 x 128, whatever the number of heads."""
    s, window, h, hkv, d = case
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, hkv, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, CausalMask(window), impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # fwd, bwd


# (length, q.k width, v's, query heads, K/V heads): the latent-attention
# cell's layer at its 16 384 tokens (192 rows a head: a tile and a half of
# the MXU's depth), the same at the model's 32 768 positions, a length that
# pads, and unequal widths on grouped K/V heads
UNEQUAL = [(16384, 192, 128, 32, 32), (32768, 192, 128, 32, 32),
           (1000, 192, 128, 4, 4), (2048, 192, 128, 8, 2),
           (2048, 64, 128, 8, 8)]
# (length, window): one map of the state-space cell's differential layers,
# 20 query heads on 10 K/V heads at q.k 64 and the pair's joined v 128
DIFFERENTIAL = [(8192, 512), (8192, None)]
# (length, window, query heads, K/V heads, q.k, v, the kernels' entry): the
# differential maps; the seventh cell's window layer, which with the window
# map above takes the slab kernels (ops/window_attention.py); the 8k cell's
# window of 4096, eight tiles wide, which keeps the walk
WINDOWED = [(s, window, 20, 10, 64, 128,
             "flash_window" if window else "_flash_causal")
            for s, window in DIFFERENTIAL] + [
    (8192, 512, 64, 8, 128, 128, "flash_window"),
    (8192, 4096, 28, 4, 128, 128, "_flash_causal")]


@pytest.mark.parametrize("case", WINDOWED,
                         ids=lambda c: "x".join(map(str, c[:-1])))
def test_a_layer_under_a_window_compiles_for_a_v5e(one_chip, monkeypatch,
                                                   case):
    """q_i on k_i and the joined v as ``models/lm.py::DifferentialAttention``
    hands them over (grouped, unequal widths, under the window and without)
    and the two decoder cells' window layers: whichever kernels the call
    takes, one ``flash_fwd`` and one ``flash_bwd`` a layer a pass, which is
    what the benchmark's readers divide the work by."""
    from test_window_kernels import _taken

    s, window, h, hkv, dqk, dv, entry = case
    taken = _taken(monkeypatch)
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, CausalMask(window), impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, s, h, dqk), shape(1, s, hkv, dqk),
        shape(1, s, hkv, dv)).compile().as_text()
    assert taken == [entry]
    assert text.count("tpu_custom_call") == 2  # flash_fwd, flash_bwd
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("case", UNEQUAL, ids=lambda c: "x".join(map(str, c)))
def test_unequal_widths_compile_for_a_v5e(one_chip, case):
    """q.k 192 deep against v 128 wide with no operand padded to a common
    width: the backward's q, dO, dq (6 + 4 + 6 MiB at 16 384) and its
    float32 scratch (12 MiB) stay inside the VMEM limit ``_call`` asks
    for, and a 192-row block passes Mosaic's tiling."""
    s, dqk, dv, h, hkv = case
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, CausalMask(), impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, s, h, dqk), shape(1, s, hkv, dqk),
        shape(1, s, hkv, dv)).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # flash_fwd, flash_bwd
    assert "flash_fwd" in text and "flash_bwd" in text


# (length, heads): the latent-attention cell's layer at its 16 384 tokens and
# 32 heads, the same at the model's 32 768 positions, and a short length
IN_PLACE = [(16384, 32), (32768, 32), (1024, 4)]


@pytest.mark.parametrize("case", IN_PLACE, ids=lambda c: "x".join(map(str, c)))
def test_a_latent_layer_s_hand_over_compiles_for_a_v5e(one_chip, monkeypatch,
                                                       case):
    """models/lm_layers.LatentAttention on the Pallas route, its gradient
    under the block's remat policy: the kernels read q after ``latent_q``'s one pass
    (forward, again, backward) and K, V and out in place: a 192-row q
    block, 128-row halves of the up-projection's 256-row product by block
    index, the keys' bfloat16 scratch (6 MiB at 16 384) beside the
    double-buffered blocks inside the VMEM limit ``_call`` asks for; and
    no float32 array as large as q is left in the compiled program."""
    import re

    from metaopt_tpu.models import lm_layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s, heads = case
    layer = lm_layers.LatentAttention(2048, lm_layers.LatentSpec(
        heads, 512, 128, 64, 128, True, 1e6), 1e-6)
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    x = on_chip(jax.ShapeDtypeStruct((1, s, 2048), jnp.float32))
    params = jax.tree.map(on_chip, jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, s, 2048)))["params"]))
    policy = jax.checkpoint_policies.save_only_these_names(
        "attention.out", "attention.lse", *(
            n for n in lm_layers.LatentSpec.KEPT.values()
            if n != "attention.kv_up"))

    def loss(p, x):
        out = jax.checkpoint(lambda p, x: layer.apply({"params": p}, x),
                             policy=policy)(p, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = lambda name: len(re.findall(  # noqa: E731
        rf'custom_call_target="tpu_custom_call".*/{name}/pallas_call', text))
    assert (calls("flash_fwd"), calls("flash_bwd"), calls("latent_q")) == (
        1, 1, 3)
    entry = text[text.index("ENTRY"):]
    assert not re.findall(
        rf" = f32\[1,(?:{heads * 192},{s}|{s},{heads},192)\]", entry)


# (length, width, query heads, K/V heads, window, q/k norm, rotary rule): the
# gated cell's window and full layers (a norm a head; the plain rule on the
# whole head, YaRN on its first half), the 8k decoder's window layer (no
# norm: the turn and the scale alone), the selected cell's heads at 16 384
# under a causal mask, and a length no tile divides
GROUPED = [(8192, 2048, 64, 8, 512, "head", (1e4, None, None, 1.0)),
           (8192, 2048, 48, 8, None, "head",
            (5e5, 64, (64.0, 4096, 64.0, 1.0), 1.4158883083359672)),
           (8192, 2560, 28, 4, 4096, None, (1.5e6, None, None, 1.0)),
           (16384, 2048, 32, 4, None, "head", (1e7, None, None, 1.0)),
           (1000, 256, 4, 2, None, "head", (1e4, 64, None, 1.0))]


@pytest.mark.parametrize("case", GROUPED, ids=lambda c: "x".join(
    str(v) for v in c[:6]))
def test_a_grouped_layer_s_hand_over_compiles_for_a_v5e(one_chip, monkeypatch,
                                                        case):
    """models/lm_layers.GroupedAttention on the Pallas route, its gradient
    under the block's remat policy: q and k reach the kernels through
    ``grouped_qk``'s one pass (forward, again, backward: 4 + 2 calls), the
    kernels read its result where it lies (no transposed and no other copy
    of an activation as large as q or k), and no float32 array as large
    as q is left in the compiled program."""
    import re

    from metaopt_tpu.models import lm_layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s, d_model, heads, kv, window, qk_norm, rule = case
    spec = lm_layers.GroupedSpec(heads, kv, 128, window, rule[0], qk_norm,
                                 None, lm_layers.Rotary(*rule))
    layer = lm_layers.GroupedAttention(d_model, spec, 1e-6)
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    x = on_chip(jax.ShapeDtypeStruct((1, s, d_model), jnp.float32))
    params = jax.tree.map(on_chip, jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, s, d_model)))["params"]))
    policy = jax.checkpoint_policies.save_only_these_names(
        "attention.out", "attention.lse", *spec.KEPT.values())

    def loss(p, x):
        out = jax.checkpoint(lambda p, x: layer.apply({"params": p}, x),
                             policy=policy)(p, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    calls = lambda name: len(re.findall(  # noqa: E731
        rf'custom_call_target="tpu_custom_call".*/{name}/pallas_call', text))
    assert (calls("flash_fwd"), calls("flash_bwd"), calls("grouped_qk"),
            calls("grouped_qk_bwd")) == (1, 1, 4, 2)
    entry = text[text.index("ENTRY"):]
    sized = "|".join(rf"{s},{n},128|{n * 128},{s}" for n in (heads, kv))
    # (the backward kernel's own float32 dK and dV a query head are read
    # out of its tuple; a copy into fast memory, S(1), moves no layout)
    assert not re.findall(
        rf" = f32\[1,(?:{sized})\]\S* (?!get-tuple-element)\w", entry)
    assert not [line for line in re.findall(
        rf" = (bf16\[1,(?:{sized})\]\S*) (?:copy|transpose)\(", entry)
        if "S(1)" not in line]


# (length, query heads, K/V heads, head width): the selected-attention
# cell's 16384 tokens (a program holds one (128, 16384) head of K and V and
# a 1 MiB slab of the packed selection), a length that pads to 256-tiles,
# and 64-wide heads
SELECTED = [(16384, 32, 4, 128), (1000, 8, 2, 128), (2048, 8, 8, 64)]


@pytest.mark.parametrize("case", SELECTED,
                         ids=lambda c: "x".join(map(str, c)))
def test_a_selected_mask_s_kernels_compile_for_a_v5e(one_chip, case):
    """The packed bits' unpacking (a sublane repeat and a shift a row) and
    the slabs' block shapes pass Mosaic; VMEM stays inside the limit."""
    from metaopt_tpu.ops.attention import SelectedMask
    from metaopt_tpu.ops.selected_attention import selected_block

    s, h, hkv, d = case
    block, s_p = selected_block(s)
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, hkv, d), jnp.bfloat16, sharding=one_chip)
    bits = jax.ShapeDtypeStruct((1, s_p // 32, s_p), jnp.int32,
                                sharding=one_chip)

    def loss(q, k, v, bits):
        out = flash_attention(q, k, v, SelectedMask(bits, block),
                              impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, bits).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # sparse_fwd, sparse_bwd
    assert "sparse_fwd" in text and "sparse_bwd" in text


@pytest.mark.parametrize("backend, kernels", [("tpu", 4), ("cpu", 0)])
def test_the_selection_compiles_for_a_v5e_at_the_cell_s_length(
        one_chip, monkeypatch, backend, kernels):
    """16384 rows of 16 x 64 index heads, top 2048: four groups of blocks,
    a fraction of a GB of temporaries (the scores of all heads at once
    would be 17 GB). As the chip takes it the scores are the Pallas kernel,
    one call a group (a (512, 6144) tile of stacked queries, a (384, 512)
    tile of stacked keys and the heads' sum pass Mosaic inside the limit it
    is given); the plain twin compiles there too."""
    import re

    from metaopt_tpu.ops import sparse_index

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    s = 16384
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, w: sparse_index.select(q, k, w, 2048)[0].bits).lower(
            shape(1, s, 16, 64), shape(1, s, 64), shape(1, s, 16)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call".*/index_scores/pallas_call',
        compiled.as_text())) == kernels


@pytest.mark.parametrize("cell", ["transformer-base-wmt",
                                  "smallthinker-21b-a3b-ep4"])
def test_the_cells_without_an_indexer_trace_no_line_of_it(one_chip,
                                                          monkeypatch, cell):
    """The two configurations that stand build and lower their loss's
    gradient, as the chip routes it, without ``ops/sparse_index.py`` ever
    being imported: what changes there cannot reach their programs."""
    import json
    import os
    import sys

    from flax import linen as nn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in [n for n in sys.modules if n.endswith("ops.sparse_index")]:
        monkeypatch.delitem(sys.modules, name)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "configs", cell + ".json")) as f:
        config = json.load(f)
    key = jax.random.PRNGKey(0)
    if cell.startswith("transformer"):
        from metaopt_tpu.models import transformer as zoo

        model = zoo.make_model({**config["script_args"], **config["hparams"],
                                "n_layers": 1})
        tokens = jnp.zeros((2, 128), jnp.int32)
        init = lambda: model.init(key, tokens, tokens, train=False)  # noqa: E731,E501
        loss = lambda p, t: zoo.loss_fn(model, p, (t, t), key)  # noqa: E731
    else:
        from chipbench import lm_config
        from metaopt_tpu.models import lm as zoo

        model = zoo.make_lm({**lm_config.description(config),
                             "num_hidden_layers": 2})
        tokens = jnp.zeros((1, 513), jnp.int32)
        init = lambda: model.init(key, tokens[:, :-1], train=False)  # noqa: E731,E501
        loss = lambda p, t: zoo.lm_loss_fn(model, p, t, key)  # noqa: E731
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: nn.meta.unbox(init()["params"])))
    text = jax.jit(jax.grad(loss)).lower(params, on_chip(tokens)).as_text()
    assert "tpu_custom_call" in text      # the route the chip takes
    assert not [n for n in sys.modules if n.endswith("ops.sparse_index")]


@pytest.mark.parametrize("how, forwards", [("kept", 2), ("bare", 4)])
def test_a_rematerialised_stack_s_gradient_holds_a_forward_kernel_a_layer(
        one_chip, monkeypatch, how, forwards):
    """What Mosaic and XLA make of a rematerialised two-layer pattern
    stack (a global and a window layer): with the rule's policy the
    compiled gradient calls ``flash_fwd`` once a layer, under a bare
    ``nn.remat`` twice; ``flash_bwd`` once either way."""
    import re

    from flax import linen as nn

    from metaopt_tpu.models import lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if how == "bare":
        monkeypatch.setattr(lm, "rematerialised",
                            lambda cls, keeps: nn.remat(cls))
    s = 256
    model = lm.make_lm(dict(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, num_hidden_layers=2, vocab_size=512,
        sliding_window_layout=[0, 1], rope_layout=[0, 1],
        sliding_window_size=128, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, moe_ffn_hidden_size=128,
        remat=True))
    on_chip = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda key: nn.meta.unbox(model.init(
            key, jnp.zeros((1, s), jnp.int32), train=False)["params"]),
        jax.random.PRNGKey(0)))
    tokens = on_chip(jax.ShapeDtypeStruct((1, s + 1), jnp.int32))
    text = jax.jit(jax.grad(lambda p, t: lm.lm_loss_fn(
        model, p, t, jax.random.PRNGKey(0)))).lower(
            params, tokens).compile().as_text()
    calls = lambda name: len(re.findall(  # noqa: E731
        rf'custom_call_target="tpu_custom_call".*/{name}/pallas_call', text))
    assert (calls("flash_fwd"), calls("flash_bwd")) == (forwards, 2)


# (tokens, heads, key width, value width): the benchmark cell's linear
# layers (15 of Olmo-Hybrid-7B's 30 heads, one row of 8192 tokens), and a
# length that pads to whole chunks
SCANS = [(8192, 15, 96, 192), (1000, 4, 96, 192)]


@pytest.mark.parametrize("shape", SCANS, ids=lambda s: "x".join(map(str, s)))
def test_the_gated_delta_rule_s_kernels_compile_for_a_v5e(one_chip, shape):
    """ops/linear_attention.py's ``linear_scan_fwd`` and ``linear_scan_bwd``
    through Mosaic: blocks of 96- and 192-wide heads, float32 products at
    precision highest for the triangular inverse, transposed operands."""
    from metaopt_tpu.ops.linear_attention import gated_delta_rule

    t, h, dk, dv = shape
    on_chip = lambda s, d: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    args = [on_chip((1, t, h, dk), jnp.bfloat16)] * 2 + [
        on_chip((1, t, h, dv), jnp.bfloat16)] + [
        on_chip((1, t, h), jnp.float32)] * 2

    def loss(q, k, v, g, beta):
        out = gated_delta_rule(q, k, v, g, beta, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "linear_scan_fwd" in text and "linear_scan_bwd" in text


# (tokens, channels, states): the state-space cell's Mamba layers (one row
# of 8192 tokens, d_inner 5120, 16 states), and channels that pad to a tile
SELECTIVE = [(8192, 5120, 16), (96, 160, 4)]


@pytest.mark.parametrize("shape", SELECTIVE,
                         ids=lambda s: "x".join(map(str, s)))
def test_the_selective_scan_s_kernels_compile_for_a_v5e(one_chip, shape):
    """ops/selective_scan.py's ``selective_scan_fwd`` and
    ``selective_scan_bwd`` through Mosaic: a tile's channels as whole
    registers, B and C as scalars from SMEM, a chunk's states (8.5 MB at
    128 tokens) inside the VMEM limit the call asks for."""
    from metaopt_tpu.ops.selective_scan import selective_scan

    t, d, n = shape
    on_chip = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)

    def loss(x, dt, a, b, c):
        return jnp.sum(selective_scan(x, dt, a, b, c, interpret=False) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        on_chip(1, t, d), on_chip(1, t, d), on_chip(d, n), on_chip(1, t, n),
        on_chip(1, t, n)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text


# (tokens, width, rows): the 8k decoder cell's lookup and the 16k
# selected-attention cell's, and a width and a table that pad
LOOKUPS = [(8192, 2560, 37984), (16384, 2048, 18992), (24, 160, 100)]


@pytest.mark.parametrize("shape", LOOKUPS, ids=lambda s: "x".join(map(str, s)))
def test_the_embedding_s_gradient_rule_compiles_for_a_v5e(one_chip, shape):
    """ops/embed.py's ``embed_rows_bwd`` through Mosaic: a token's row out
    of a block in VMEM by its sublane, a block's ids in SMEM, the table
    written in whole (8, 128) tiles by copies the kernel starts itself."""
    from metaopt_tpu.ops.embed import embed_rows

    tokens, width, rows = shape
    table = jax.ShapeDtypeStruct((rows, width), jnp.float32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one_chip)

    def loss(table, ids):
        return jnp.sum(embed_rows(table, ids, interpret=False).astype(
            jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss)).lower(table, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "embed_rows_bwd" in text and "scatter" not in text


# (buffer rows, d, f, activation, held experts): the held experts' part of
# the 16k selected-attention cell, the latent cell and the 8k decoder;
# experts twice as wide as any cell's, where the tiles have to give way to
# VMEM (the gating's 512 rows a program would take 28 MiB there); and the
# gated mixed-window cell's: 8192 tokens x top 8, 32 held experts 512 wide
EXPERTS = [(131072, 2048, 768, "silu", 16), (98304, 2048, 768, "silu", 16),
           (49152, 2560, 768, "relu", 16), (32768, 4096, 1536, "silu", 16),
           (65536, 2048, 512, "silu", 32)]


@pytest.mark.parametrize("shape", EXPERTS, ids=lambda s: "x".join(map(str, s)))
def test_the_held_experts_part_compiles_for_a_v5e(one_chip, shape):
    """models/moe.py's ``_held_experts`` and its gradient on the chip's
    route, through Mosaic at the cells' own sizes and the tiles the layer
    chooses: six megablox calls, the gating and its gradient with a grid
    read on the device. The compiled program makes no transposed and no
    other copy of a buffer: the turn a weight gradient gives its left
    operand and the one megablox's ``tgmm`` gives it back are cancelled,
    and the kernel reads ``rows`` and ``h`` as they lie."""
    import re

    from flax import linen as nn

    from metaopt_tpu.models import moe

    n, d, f, activation, held = shape
    act = {"relu": nn.relu, "silu": nn.silu}[activation]
    on_chip = lambda s, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)

    def loss(rows, w_gu, w_down, items):
        out = moe._held_experts(rows, w_gu, w_down, items, jnp.sum(items),
                                act, "megablox")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip((n, d), jnp.bfloat16),
        on_chip((held, d, 2 * f), jnp.bfloat16),
        on_chip((held, f, d), jnp.bfloat16),
        on_chip((held,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 8
    assert "expert_gating_bwd" in text
    buffers = re.compile(rf"= \w+\[(\d+,)?{n}(,\d+)?\]\S* (transpose|copy)\(")
    assert [line for line in text.splitlines() if buffers.search(line)] == []


# (tokens, heads, groups, state width, head width): the one-sublayer cell's
# Mamba-2 blocks (64 heads of 64 on 8 groups' B and C of 128, one row of 8192
# tokens), and a length that pads to whole chunks
DECAYS = [(8192, 64, 8, 128, 64), (1000, 16, 2, 128, 64)]


@pytest.mark.parametrize("shape", DECAYS, ids=lambda s: "x".join(map(str, s)))
def test_the_scalar_decay_rule_s_kernels_compile_for_a_v5e(one_chip, shape):
    """ops/linear_attention.py's ``ssd_scan_fwd`` and ``ssd_scan_bwd``
    through Mosaic: a program a (row, group, chunk), a group's eight heads'
    64-wide values and states as blocks of their own, transposed
    operands."""
    from metaopt_tpu.ops.linear_attention import scalar_decay_rule

    t, h, g, n, p = shape
    on_chip = lambda s, d: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    args = [on_chip((1, t, g, n), jnp.bfloat16)] * 2 + [
        on_chip((1, t, h, p), jnp.bfloat16), on_chip((1, t, h), jnp.float32)]

    def loss(q, k, v, g):
        out = scalar_decay_rule(q, k, v, g, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


@pytest.mark.parametrize("shape", DECAYS, ids=lambda s: "x".join(map(str, s)))
def test_the_mamba2_hand_over_s_calls_compile_for_a_v5e(one_chip, shape):
    """ops/ssd_hand_over.py's four calls through Mosaic at the cell's
    shapes (4 taps) and on a length no tile divides: the halo blocks of 16
    rows over the product, the rows shifted a tap down the sublanes, a
    head's step spread over its channels on the MXU, the clamped blocks of
    the front calls' column axis."""
    from metaopt_tpu.ops import ssd_hand_over as sh

    t, h, g, n, p = shape
    sz = sh.Sizes(h, p, g, n)
    wide = sz.inner + 2 * sz.bc
    on_chip = lambda s, d: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    f32 = lambda *s: on_chip(s, jnp.float32)  # noqa: E731
    args = [on_chip((1, t, sz.inner + wide), jnp.bfloat16), f32(1, t, h),
            f32(4, wide), f32(wide), f32(h), f32(h), f32(h), f32(sz.inner),
            on_chip((1, t, h, p), jnp.bfloat16)]

    def loss(zxbc, dt, taps, bias, dt_bias, a_log, d, weight, y):
        c, b, v, decay, z, x = sh.ssd_operands(zxbc, dt, taps, bias, dt_bias,
                                               a_log, sz)
        out = sh.ssd_gated_norm(y + v, z, x, jax.lax.stop_gradient(zxbc),
                                taps, bias, d, weight, sz, 1e-5)
        return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                   for o in (out, c, b, decay))

    text = jax.jit(jax.grad(loss, argnums=tuple(range(9)))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("ssd_operands", "ssd_operands_bwd", "ssd_gated_norm",
                 "ssd_gated_norm_bwd"):
        assert f"%{name}.1 = " in text, name


def test_two_matrix_experts_of_an_odd_width_compile_for_a_v5e(one_chip):
    """models/moe.py's ``_held_experts`` without a gate at the one-sublayer
    cell's sizes (8192 tokens x top 6, 8 held experts 1856 = 29 x 64 wide
    on a model 2688 wide): six megablox calls whose every block takes the
    width whole, the activation's pass and its gradient."""
    from metaopt_tpu.models import moe
    from metaopt_tpu.models.lm_layers import ACTIVATIONS

    n, d, f, held = 49152, 2688, 1856, 8
    on_chip = lambda s, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)

    def loss(rows, w_up, w_down, items):
        out = moe._held_experts(rows, w_up, w_down, items, jnp.sum(items),
                                ACTIVATIONS["relu2"], "megablox", False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip((n, d), jnp.bfloat16), on_chip((held, d, f), jnp.bfloat16),
        on_chip((held, f, d), jnp.bfloat16),
        on_chip((held,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 8
    assert "expert_activation_bwd" in text


#: (tokens, heads, key width, value width): the hybrid cell's linear layers;
#: a length that is no whole tile; narrower heads of whole sublane tiles
DELTA_SHAPES = [(8192, 15, 96, 192), (1408, 4, 96, 192), (2048, 2, 16, 48)]


@pytest.mark.parametrize("shape", DELTA_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_the_gated_delta_hand_over_s_calls_compile_for_a_v5e(one_chip, shape):
    """ops/delta_hand_over.py's four calls through Mosaic at the cell's
    shapes (4 taps): blocks of a head whose 96 or 192 columns are the
    array's own and no whole lanes, the halo blocks of 16 rows before and
    after a tile, the rows shifted a tap down the sublanes, the norms' sums
    over part of a register's lanes; and between the products, which the
    caller has tokens first, and the calls no copy that XLA must make (the
    turn to heads first is the layout of whatever produces them)."""
    from metaopt_tpu.ops import delta_hand_over as dh

    t, h, dk, dv = shape
    on_chip = lambda s, d: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    product = lambda d: on_chip((1, t, h, d), jnp.bfloat16)  # noqa: E731
    taps = lambda d: on_chip((4, h, d), jnp.float32)  # noqa: E731
    args = [product(dk), product(dk), product(dv), taps(dk), taps(dk),
            taps(dv), product(dv), on_chip((dv,), jnp.float32)]

    def loss(q, k, v, tq, tk, tv, g, scale):
        qh, kh, vh = dh.delta_operands(q, k, v, tq, tk, tv)
        o = vh + jnp.sum(qh, -1, keepdims=True) + jnp.sum(kh, -1,
                                                          keepdims=True)
        out = dh.delta_gated_norm(o, g, scale, 1e-6)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=tuple(range(8)))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("delta_operands", "delta_operands_bwd", "delta_gated_norm",
                 "delta_gated_norm_bwd"):
        assert f"{name}" in text, name


#: (rows, tokens, channels): the short-convolution cell's mixers; a length
#: that is no whole tile of either direction; one block of columns
SHORT_CONVS = [(1, 8192, 2048), (2, 1200, 1024), (1, 512, 128)]


@pytest.mark.parametrize("shape", SHORT_CONVS,
                         ids=lambda s: "x".join(map(str, s)))
def test_the_gated_short_convolution_s_calls_compile_for_a_v5e(one_chip,
                                                               shape):
    """ops/short_conv.py's two calls through Mosaic at the cell's shapes (3
    taps): a tile's block of the product's 3 D columns whole, the halo
    blocks of 16 rows before and after it, the rows shifted a tap down the
    sublanes, the taps' sums a tile; and between the input projection's
    product and the calls, and between them and the output projection's
    operand, no slice, transpose or copy that XLA must make: the projection
    and its gradient are the two custom calls' only neighbours."""
    from metaopt_tpu.ops import short_conv as sc

    b, t, d = shape
    on_chip = lambda s, dt: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)

    def loss(u, w_in, taps, w_out):
        bcx = jnp.dot(u, w_in, preferred_element_type=jnp.bfloat16)
        y = sc.gated_short_conv(bcx, taps)
        return jnp.sum(jnp.dot(
            y, w_out, preferred_element_type=jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        on_chip((b, t, d), jnp.bfloat16), on_chip((d, 3 * d), jnp.bfloat16),
        on_chip((3, d), jnp.float32),
        on_chip((d, d), jnp.bfloat16)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "short_conv_fwd" in text and "short_conv_bwd" in text
    entry = text[text.index("ENTRY"):]
    assert " transpose(" not in entry and " slice(" not in entry
    assert " copy(" not in entry
