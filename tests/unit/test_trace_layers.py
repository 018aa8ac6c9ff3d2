"""A train step's operations under the trace layer's two rules
(metaopt_tpu/utils/trace.py): ``SCOPES`` closes over the step's source, so
every operation the program writes has a layer (``layer_of``) and a
direction (``direction``), in the forward, its second run under remat and
the backward.

The steps are the benchmark's nine model kinds at their rehearsal
sizes, lowered and not compiled. Each is lowered twice: as this backend
routes it, and for the TPU with ``jax.default_backend`` answering "tpu", so
that the Pallas kernels are on the path (lowering a kernel for the TPU needs
no chip).
"""

import importlib
import json
import os
import re

import pytest

from metaopt_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: kind -> (the cell's configuration, the chipbench module that turns it
#: into the program's description; None: ``steady_steps.model_hparams``)
KINDS = {
    "2017-base": ("transformer-base-wmt", None),
    "pattern-decoder": ("smallthinker-21b-a3b-ep4", "lm_config"),
    "selected-attention-decoder": ("keye-vl2-30b-a3b-ep8",
                                   "sparse_lm_config"),
    "hybrid-decoder": ("olmo-hybrid-7b-tp2", "hybrid_lm_config"),
    "latent-decoder": ("kanana-2-30b-a3b-ep8", "mla_lm_config"),
    "state-space-decoder": ("phi-4-mini-flash-vp8", "ssm_lm_config"),
    "gated-decoder": ("laguna-xs2-33b-a3b-ep8", "gated_lm_config"),
    "one-sublayer-decoder": ("nemotron-3-nano-30b-a3b-ep16",
                             "ssd_lm_config"),
    "short-conv-decoder": ("lfm2-24b-a2b-ep8", "conv_lm_config"),
}
#: no operation of the device: a literal, a function's end, and remat's own
#: barrier around a block's kept values (jax names it ``.../remat2``)
NOT_OPERATIONS = ("stablehlo.constant", "func.return", "stablehlo.return",
                  "stablehlo.optimization_barrier")


def _rehearsal_config(name):
    from chipbench.run import rehearsal_sizes

    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        config = json.load(f)
    rehearsal_sizes(config)
    return config


def _one_device():
    """The cell's mesh: one device, whatever the test process has."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))


def _lower_2017(config, lower):
    import jax
    import jax.numpy as jnp

    from metaopt_tpu.models.transformer import (
        init_sharded, make_model, make_train_step, trial_setup,
    )
    from metaopt_tpu.parallel.mesh import use_mesh

    from chipbench.runners.steady_steps import model_hparams

    a, hp = config["script_args"], model_hparams(config)
    mesh, tx = trial_setup(hp, _one_device(), 1, 1, 1, hp["schedule_steps"])
    model = make_model(hp)
    shape = (a["batch_size"], a["seq_len"])
    with use_mesh(mesh):
        params, opt_state, _ = init_sharded(model, mesh, tx, shape, 0)
        rows = jnp.ones(shape, jnp.int32)
        return lower(jax.jit(make_train_step(model, tx)), params, opt_state,
                     (rows, rows), jax.random.PRNGKey(0))


def _lower_decoder(config, module, lower):
    import jax

    from metaopt_tpu.models.lm import LMTrial

    a = config["script_args"]
    description = importlib.import_module("chipbench." + module).description
    trial = LMTrial(description(config), mesh=_one_device(),
                    n_train=a["n_train"],
                    batch_size=a["batch_size"], seq_len=a["seq_len"],
                    steps=config["hparams"]["schedule_steps"], seed=1)
    with trial:
        return lower(trial._step_fn, trial.params, trial.opt_state,
                     trial.counts, trial.rows(0), jax.random.PRNGKey(0))


def operations(module):
    """[(kind, op_name)] of the operations of a lowered module, an inner
    function's under each of its calls: jax names them relative to the
    function and XLA, inlining the call, puts the call's own ``op_name`` in
    front (the chip's trace reads ``.../attention.index/jit(_scores_pallas)/
    index_scores/pallas_call``)."""
    functions = {}
    for op in module.body.operations:
        if op.operation.name == "func.func":
            functions[str(op.attributes["sym_name"]).strip('"')] = op

    def name_of(op):
        # loc("jit(train_step)/.../mul"(callsite(...))); a call of a closed
        # function wraps its name once more: loc("closed_call:"("jit(...
        return next((name for name in re.findall(
            r'"([^"]*)"\(', str(op.location)) if not name.endswith(":")), "")

    out = []

    def walk(op, prefix):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    kind = inner.operation.name
                    if kind in NOT_OPERATIONS:
                        continue
                    name = "/".join(filter(None, (prefix, name_of(inner))))
                    if kind == "func.call":
                        callee = str(inner.attributes["callee"]).lstrip("@")
                        walk(functions[callee].operation, name)
                        continue
                    out.append((kind, name))
                    walk(inner.operation, prefix)

    walk(functions["main"].operation, "")
    return out


@pytest.fixture(scope="module")
def lowered_steps():
    """kind -> {"here": operations as this backend routes the step, "tpu":
    with the Pallas kernels on the path}, each lowered once a module."""
    import jax

    def lower_for(kind, tpu):
        name, module = KINDS[kind]
        config = _rehearsal_config(name)

        def lower(step, *args):
            if not tpu:
                return step.lower(*args)
            return step.trace(*args).lower(lowering_platforms=("tpu",))

        with pytest.MonkeyPatch.context() as patch:
            if tpu:
                patch.setattr(jax, "default_backend", lambda: "tpu")
            lowered = _lower_2017(config, lower) if module is None \
                else _lower_decoder(config, module, lower)
        return operations(lowered.compiler_ir())

    made = {}

    def get(kind, route):
        if (kind, route) not in made:
            made[kind, route] = lower_for(kind, route == "tpu")
        return made[kind, route]

    return get


@pytest.mark.parametrize("route", ["here", "tpu"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_every_operation_of_a_train_step_has_a_layer(lowered_steps, kind,
                                                     route):
    """The invariant ``SCOPES`` is closed under: no ``op_name`` of the
    step lies outside every name of it."""
    ops = lowered_steps(kind, route)
    assert len(ops) > 100
    assert all(name.startswith("jit(train_step)") for _, name in ops)
    outside = sorted({name for _, name in ops
                      if trace.layer_of(name) is None})
    assert outside == []


@pytest.mark.parametrize("kind, remat", [
    ("2017-base", False), ("pattern-decoder", True),
    ("selected-attention-decoder", True), ("hybrid-decoder", True),
    ("latent-decoder", True), ("state-space-decoder", True),
    ("gated-decoder", True), ("one-sublayer-decoder", True),
    ("short-conv-decoder", True)])
def test_a_step_has_operations_in_every_direction_it_runs(lowered_steps,
                                                         kind, remat):
    """Forward, backward and update in every step; the forward's second
    run where the blocks are rematerialised and nowhere else; and under
    each direction the trunk's three new names."""
    by_direction = {}
    for _, name in lowered_steps(kind, "here"):
        by_direction.setdefault(trace.direction(name), set()).add(
            trace.layer_of(name))
    assert set(by_direction) == set(trace.DIRECTIONS) - (
        set() if remat else {"forward.again"})
    assert by_direction["update"] == {"optimizer"}
    assert {"norm", "residual", "loss"} <= by_direction["forward"]
    assert {"norm", "loss"} <= by_direction["backward"]
    if remat:
        assert "norm" in by_direction["forward.again"]
        # a block of one sublayer ends in its one sum, which no backward
        # pass reads: its second run makes none
        assert ("residual" in by_direction["forward.again"]) \
            == (kind != "one-sublayer-decoder")
        # the loss, the embedding and the head are outside the blocks
        assert not {"loss", "embed", "readout_xent", "optimizer"} \
            & by_direction["forward.again"]


@pytest.mark.parametrize("kind, kernel, forward, backward", [
    ("pattern-decoder", "flash_fwd", 4, 0),
    ("pattern-decoder", "flash_bwd", 0, 4),
    ("selected-attention-decoder", "sparse_fwd", 4, 0),
    ("selected-attention-decoder", "sparse_bwd", 0, 4),
    ("hybrid-decoder", "linear_scan_fwd", 3, 0),
    ("hybrid-decoder", "linear_scan_bwd", 0, 3),
    ("hybrid-decoder", "flash_fwd", 1, 0),
    ("hybrid-decoder", "flash_bwd", 0, 1),
    ("latent-decoder", "flash_fwd", 5, 0),
    ("latent-decoder", "flash_bwd", 0, 5),
    ("state-space-decoder", "selective_scan_fwd", 2, 0),
    ("state-space-decoder", "selective_scan_bwd", 0, 2),
    # two maps a layer of differential attention, three such layers
    ("state-space-decoder", "flash_fwd", 6, 0),
    ("state-space-decoder", "flash_bwd", 0, 6),
    # two full and three window layers, one call each at its own head count
    ("gated-decoder", "flash_fwd", 5, 0),
    ("gated-decoder", "flash_bwd", 0, 5),
    # four Mamba-2 blocks and one attention block of the nine
    ("one-sublayer-decoder", "ssd_scan_fwd", 4, 0),
    ("one-sublayer-decoder", "ssd_scan_bwd", 0, 4),
    ("one-sublayer-decoder", "flash_fwd", 1, 0),
    ("one-sublayer-decoder", "flash_bwd", 0, 1),
    # two attention layers of the seven (the five short-convolution
    # mixers' core is the plain twin at a rehearsal's 64 channels:
    # tests/unit/test_short_conv_kernel.py has the rule)
    ("short-conv-decoder", "flash_fwd", 2, 0),
    ("short-conv-decoder", "flash_bwd", 0, 2),
    ("short-conv-decoder", "embed_rows_bwd", 0, 1),
    # the embedding's gradient rule: one table, written once
    ("one-sublayer-decoder", "embed_rows_bwd", 0, 1),
    ("pattern-decoder", "embed_rows_bwd", 0, 1),
    ("selected-attention-decoder", "embed_rows_bwd", 0, 1),
    ("hybrid-decoder", "embed_rows_bwd", 0, 1),
    ("latent-decoder", "embed_rows_bwd", 0, 1),
    ("state-space-decoder", "embed_rows_bwd", 0, 1),
    ("gated-decoder", "embed_rows_bwd", 0, 1),
])
def test_a_kernel_s_calls_by_direction(lowered_steps, kind, kernel, forward,
                                       backward):
    """A kept-output kernel runs in the forward only (a rematerialised
    block keeps what it made: never ``forward.again``), a backward rule's
    kernel in the backward only, each once a layer that calls it."""
    calls = [name for k, name in lowered_steps(kind, "tpu")
             if kernel in name.split("/") and "custom_call" in k]
    by_direction = {d: sum(trace.direction(n) == d for n in calls)
                    for d in trace.DIRECTIONS}
    assert by_direction == {"forward": forward, "forward.again": 0,
                            "backward": backward, "update": 0}
    layer = {"linear": "linear_attention", "select": "ssm", "ssd_sc": "ssd",
             "embed_": "embed"}.get(kernel[:6], "attention")
    assert {trace.layer_of(n) for n in calls} == {layer}


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "2017-base"])
def test_the_lookup_and_its_gradient_rule_are_the_embedding_s(lowered_steps,
                                                             kind):
    """ops/embed.py's two rules under the scope ``embed``: the gather in
    the forward; the sort, the sorted rows and the kernel in the backward
    (``transpose(`` with the rule's own scope), and no scatter-add."""
    ops = [(k, n) for k, n in lowered_steps(kind, "tpu")
           if trace.layer_of(n) == "embed"]
    by_direction = {}
    for k, n in ops:
        by_direction.setdefault(trace.direction(n), set()).add(
            k.split(".")[-1])
    assert set(by_direction) == {"forward", "backward"}
    assert "gather" in by_direction["forward"]
    assert {"sort", "gather", "custom_call"} <= by_direction["backward"]
    assert "scatter" not in by_direction["backward"]
    # off the chip the plain lookup stands, and autodiff's scatter-add
    plain = {k.split(".")[-1] for k, n in lowered_steps(kind, "here")
             if trace.layer_of(n) == "embed"
             and trace.direction(n) == "backward"}
    assert "scatter" in plain and "custom_call" not in plain


@pytest.mark.parametrize("route", ["here", "tpu"])
def test_a_short_convolution_mixer_s_operations_by_scope(lowered_steps,
                                                        route):
    """Five mixers: under ``short_conv`` the two projections and the core,
    under ``short_conv.core`` the gates and the taps alone (no product), in
    the forward, its second run and the backward; ``short_conv`` is a
    top-level layer of the partition."""
    assert "short_conv" in trace.LAYERS and "short_conv.core" in trace.SCOPES
    ops = [(k, n) for k, n in lowered_steps("short-conv-decoder", route)
           if trace.layer_of(n) == "short_conv"]
    core = [(k, n) for k, n in ops if "short_conv.core" in re.split(
        r"[/()]", n)]
    assert core and len(core) < len(ops)
    assert not [k for k, _ in core if k.endswith("dot_general")]
    products = [n for k, n in ops if k.endswith("dot_general")]
    assert {trace.direction(n) for n in products} \
        >= {"forward", "backward"}
    assert {trace.direction(n) for _, n in core} \
        == {"forward", "forward.again", "backward"}
    blocks = {part for _, n in ops for part in n.split("/")
              if re.fullmatch(r"h\d+", part)}
    assert blocks == {"h1", "h3", "h4", "h5", "h7"}


@pytest.mark.parametrize("kind, helper", [
    ("pattern-decoder", "_causal_backward"),
    ("selected-attention-decoder", "_backward"),
    ("hybrid-decoder", "_bwd_pallas"),
    ("latent-decoder", "_backward"),
    ("one-sublayer-decoder", "_decay_bwd_pallas"),
])
def test_a_backward_rule_s_helpers_are_backward(lowered_steps, kind, helper):
    """What a kernel's backward rule runs around the kernel (the delta,
    the sum of a K/V head's gradients, the flips and pads of the scan) is
    traced under ``transpose(`` with the rule's own scope."""
    under = [name for _, name in lowered_steps(kind, "tpu")
             if f"jit({helper})" in name]
    assert under
    assert {trace.direction(n) for n in under} == {"backward"}


# -- the two rules on literal paths, as the lowerings above and the chip's
# traces have them -----------------------------------------------------------

_BLOCK = ("jit(train_step)/transpose(jvp(DecoderOnlyLM))/DecoderOnlyLM."
          "_patterned/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/checkpoint/")


@pytest.mark.parametrize("op_name, layer, direction", [
    # a kernel's backward rule
    (_BLOCK + "h0/attn/attention/attention.core/jit(_causal_backward)/"
     "flash_bwd/pallas_call", "attention", "backward"),
    # a rematerialised block's second run
    (_BLOCK + "rematted_computation/h2/experts/moe/moe.experts/"
     "dot_general", "moe", "forward.again"),
    (_BLOCK + "rematted_computation/h1/norm_post/norm/rsqrt", "norm",
     "forward.again"),
    # the compiler's own: no name at all, or a name without a scope
    ("", None, "forward"),
    ("jit(train_step)/transpose(jvp(DecoderOnlyLM))/DecoderOnlyLM."
     "_patterned/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/remat2", None,
     "backward"),
    # a part of a layer inside a transform's brackets
    ("jit(train_step)/transpose(jvp(attention.core))/while/body/"
     "dot_general", "attention", "backward"),
    ("jit(train_step)/transpose(jvp(readout_xent))/jit(_take)/gather",
     "readout_xent", "backward"),
    # q/k norms inside attention stay attention's: the outermost name owns
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h0/attn/"
     "attention/q_norm/norm/mul", "attention", "forward"),
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h1/"
     "linear/linear_attention/norm/norm/rsqrt", "linear_attention",
     "forward"),
    # a Mamba-2 mixer's gated norm and convolution are the mixer's, its
    # scan the mixer's part; the one norm of a block of one sublayer
    (_BLOCK + "h0/ssd/ssd/ssd.core/jit(_decay_bwd_pallas)/ssd_scan_bwd/"
     "pallas_call", "ssd", "backward"),
    (_BLOCK + "rematted_computation/h2/ssd/ssd/rsqrt", "ssd",
     "forward.again"),
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h1/"
     "norm_post/norm/rsqrt", "norm", "forward"),
    # a kept-output kernel, the index scores': forward only
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h3/attn/"
     "attention/indexer/attention.index/jit(_scores_pallas)/index_scores/"
     "pallas_call", "attention", "forward"),
    # a latent layer's own part of attention, its norm included; the
    # shared experts' feed-forward is the expert layer's, not ``ffn``'s
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h0/attn/"
     "attention/attention.latent/kv_a_norm/norm/rsqrt", "attention",
     "forward"),
    (_BLOCK + "rematted_computation/h1/attn/attention/attention.latent/"
     "kv_b/dot_general", "attention", "forward.again"),
    (_BLOCK + "h1/experts/moe/moe.shared/shared/ffn/down/dot_general",
     "moe", "backward"),
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h0/mlp/"
     "ffn/gate/dot_general", "ffn", "forward"),
    # the trunk
    ("jit(train_step)/jvp(DecoderOnlyLM)/DecoderOnlyLM._patterned/h0/"
     "residual/add", "residual", "forward"),
    ("jit(train_step)/jvp(loss)/reduce_sum", "loss", "forward"),
    ("jit(train_step)/transpose(jvp(loss))/div", "loss", "backward"),
    ("jit(train_step)/loss/add", "loss", "forward"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer",
     "update"),
    # an evaluation's function is wholly under its scope (models/resnet.py)
    ("jit(val_error)/eval/ResNet/conv_general_dilated", "eval", "forward"),
    # a gated short convolution: the core's backward rule names its own
    # scope; the mixer's projection under the layer alone
    (_BLOCK + "h3/conv/short_conv/short_conv.core/jit(_backward)/"
     "short_conv_bwd/pallas_call", "short_conv", "backward"),
    (_BLOCK + "rematted_computation/h3/conv/short_conv/in_proj/dot_general",
     "short_conv", "forward.again"),
    # ``attention`` does not match inside another word
    ("jit(train_step)/jvp(Transformer)/dec0/self_attention_like/mul", None,
     "forward"),
])
def test_the_two_rules_on_a_path(op_name, layer, direction):
    assert trace.layer_of(op_name) == layer
    assert trace.direction(op_name) == direction


def test_the_layers_are_the_top_level_scopes_and_every_scope_has_one():
    assert len(trace.SCOPES) == len(set(trace.SCOPES)) == 30
    assert {"attention.latent", "moe.shared", "ssm.core", "ssd.core",
            "attention.diff", "attention.gate", "short_conv.core"} \
        <= set(trace.SCOPES)
    assert set(trace.LAYERS) == {
        "embed", "attention", "ffn", "moe", "linear_attention", "ssm", "ssd",
        "short_conv", "gmu", "readout_xent", "optimizer", "eval", "norm",
        "residual", "loss"}
    for scope in trace.SCOPES:
        assert trace.layer_of(scope) in trace.LAYERS
        assert trace.layer_of(f"jit(train_step)/jvp({scope})/mul") \
            == scope.partition(".")[0]
