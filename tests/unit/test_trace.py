"""The program's one trace layer (metaopt_tpu/utils/trace.py): spans, the
ring, the dump, compile records, device scope names, and its call sites in
the executor, the producer and the trial."""

import json
import os
import subprocess
import sys
import time

import pytest

from metaopt_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _python(code, tmp_path, **env):
    """Run ``code`` in a fresh interpreter from ``tmp_path``."""
    full = {k: v for k, v in os.environ.items()
            if k not in (trace.PROFILE_DIR_ENV, "METAOPT_TPU_TRIAL_INFO")}
    full.update(PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=full,
                          capture_output=True, text=True, timeout=120)


def _spans_under(base):
    return trace.load(str(base))


# -- spans and the ring -------------------------------------------------------


def test_nesting_gives_parent_ids_and_the_trial_is_inherited():
    with trace.span("worker.trial", id="T-1", trial="T-1") as root:
        with trace.span("executor.spawn") as spawn:
            info = trace.spawn_info()
        with trace.span("executor.collect", n=3) as collect:
            pass
    assert root["parent"] is None and root["id"] == "T-1"
    assert spawn["parent"] == collect["parent"] == "T-1"
    assert spawn["trial"] == collect["trial"] == "T-1"
    assert info["span"] == spawn["id"] != collect["id"]
    assert spawn["start_ns"] <= info["spawn_ns"] <= spawn["end_ns"]
    assert collect["attrs"] == {"n": 3} and collect["pid"] == os.getpid()
    assert root["start_ns"] <= spawn["start_ns"] <= spawn["end_ns"] \
        <= collect["start_ns"] <= root["end_ns"]
    # children close first, so they precede their parent in the ring
    assert [s["id"] for s in trace.spans()[-3:]] == [
        spawn["id"], collect["id"], "T-1"]


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(AssertionError):
        with trace.span("worker.lunch"):
            pass
    with pytest.raises(AssertionError):
        trace.scope("attention.kore")


def test_the_ring_is_bounded():
    for _ in range(trace.RING + 50):
        trace.record("worker.reserve", 1, 2)
    assert len(trace.spans()) == trace.RING


def test_a_long_train_loop_does_not_push_the_phases_out_of_the_ring():
    """Per-step spans are summed into the span around the loop; only a step
    with a child of its own (the one that compiled) stays whole."""
    steps = trace.RING + 50
    with trace.span("trial.setup") as setup:
        pass
    with trace.span("trial.train", steps=steps) as train:
        for i in range(steps):
            with trace.span("slice_and_shard_batch"):
                pass
            with trace.span("dispatch_step") as step:
                if i == 0:
                    compiled = trace.record("compile", step["start_ns"],
                                            time.time_ns(), fn="train_step")
    assert setup in trace.spans("trial.setup")
    (whole,) = [s for s in trace.spans("dispatch_step")
                if s["parent"] == train["id"]]
    assert compiled["parent"] == whole["id"]
    n_slices, slice_s = train["attrs"]["per_step"]["slice_and_shard_batch"]
    n_steps, step_s = train["attrs"]["per_step"]["dispatch_step"]
    assert (n_slices, n_steps) == (steps, steps - 1)
    assert 0 < slice_s + step_s + trace.seconds(whole) < trace.seconds(train)
    # outside any span there is nothing to sum into: kept whole
    with trace.span("dispatch_step") as bare:
        pass
    assert trace.spans()[-1] is bare


def test_import_and_a_span_do_not_import_jax(tmp_path):
    done = _python(
        "import sys\n"
        "from metaopt_tpu.utils import trace\n"
        "import metaopt_tpu.client, metaopt_tpu.executor.subproc\n"
        "with trace.span('worker.reserve'):\n"
        "    pass\n"
        "assert len(trace.spans('worker.reserve')) == 1\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))\n",
        tmp_path)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "[]"


_THREE_SPANS = (
    "from metaopt_tpu.utils import trace\n"
    "trace.owner('w7')\n"
    "with trace.span('worker.trial', id='T', trial='T'):\n"
    "    with trace.span('worker.report', status='broken'):\n"
    "        pass\n")


def test_nothing_is_written_without_a_profile_directory(tmp_path):
    done = _python(_THREE_SPANS, tmp_path)
    assert done.returncode == 0, done.stderr
    assert os.listdir(tmp_path) == []


def test_the_dump_is_json_lines_under_the_owner_s_name(tmp_path):
    out = tmp_path / "prof"
    done = _python(_THREE_SPANS, tmp_path, **{trace.PROFILE_DIR_ENV: str(out)})
    assert done.returncode == 0, done.stderr
    assert os.listdir(out) == ["w7"]
    rows = [json.loads(line) for line in open(out / "w7" / "spans.jsonl")]
    assert [r["name"] for r in rows] == ["worker.report", "worker.trial"]
    assert set(rows[0]) == {"name", "start_ns", "end_ns", "id", "parent",
                            "trial", "pid", "attrs"}
    assert rows[0]["parent"] == "T" and rows[0]["attrs"] == {
        "status": "broken"}


# -- compile records ----------------------------------------------------------


def test_a_jitted_function_yields_one_compile_span_then_none():
    import jax
    import jax.numpy as jnp

    def a_function_of_this_test(x):
        return (x * 3 + 1).sum()

    mine = lambda: [s for s in trace.spans("compile")  # noqa: E731
                    if s["attrs"]["fn"] == "a_function_of_this_test"]
    trace.watch_compiles()
    jitted = jax.jit(a_function_of_this_test)
    with trace.span("trial.init") as over:
        jitted(jnp.ones((7, 3))).block_until_ready()
    (rec,) = mine()
    assert rec["parent"] == over["id"]
    assert over["start_ns"] <= rec["start_ns"] <= rec["end_ns"] \
        <= over["end_ns"]
    assert rec["attrs"]["cache_hit"] in (True, False)
    assert {"trace_s", "lower_s", "backend_s"} <= set(rec["attrs"])
    jitted(jnp.ones((7, 3))).block_until_ready()  # the executable is there
    assert len(mine()) == 1


# -- device scopes ------------------------------------------------------------


@pytest.fixture(scope="module", params=["reference", "chunked"])
def lowered_op_names(request):
    """Every ``op_name`` in the lowered train step at rehearsal size on a
    mesh of one device, as the benchmark's cell has it, with attention
    through the plain path (the CPU's) and through the chunked
    ``custom_vjp`` one (the TPU's). (On a larger mesh attention runs under
    ``shard_map``, whose body's names start anew at ``attention.core/``.)"""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from metaopt_tpu.models.transformer import (
        init_sharded, make_model, make_train_step, trial_setup,
    )
    from metaopt_tpu.ops import attention
    from metaopt_tpu.parallel.mesh import use_mesh

    with pytest.MonkeyPatch.context() as patch:
        # the chunked twin at dropout 0, as no backend routes it: the step
        # stays the one the reference case lowers, but for attention
        patch.setattr(attention, "attention_route",
                      lambda rate, mesh=None: request.param)
        hp = dict(d_model=64, n_layers=1, d_ff=128, n_heads=1, vocab=512,
                  max_len=32, dropout=0.0)
        one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
        mesh, tx = trial_setup(hp, one, 1, 1, 1, 100)
        model = make_model(hp)
        with use_mesh(mesh):
            params, opt_state, _ = init_sharded(model, mesh, tx, (8, 16), 0)
            rows = jnp.ones((8, 16), jnp.int32)
            text = jax.jit(make_train_step(model, tx)).lower(
                params, opt_state, (rows, rows), jax.random.PRNGKey(0),
            ).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def _under(names, scope):
    import re

    at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    return [n for n in names if at.search(n)]


@pytest.mark.parametrize("scope", [
    s for s in trace.SCOPES if s != "eval" and not s.startswith("moe")
    # a decoder's selected-attention layers': tests/unit/test_lm_selected.py;
    # its linear-attention layers': tests/unit/test_lm_hybrid.py; its
    # latent layers' (and ``moe.shared``): tests/unit/test_lm_latent.py
    # its state-space, memory-unit and differential layers':
    # tests/unit/test_trace_layers.py; the gate on a grouped layer's output:
    # tests/unit/test_lm_gated.py; its Mamba-2 mixers' and its gated short
    # convolutions': tests/unit/test_trace_layers.py
    and s not in ("attention.index", "attention.select", "linear_attention",
                  "linear_attention.core", "attention.latent", "ssm",
                  "ssm.core", "gmu", "attention.diff", "attention.gate",
                  "ssd", "ssd.core", "short_conv", "short_conv.core")])
def test_every_scope_names_ops_of_the_train_step(lowered_op_names, scope):
    assert _under(lowered_op_names, scope), scope


@pytest.mark.parametrize(
    "scope", ["embed", "attention", "attention.core", "ffn", "readout_xent"])
def test_backward_ops_carry_the_scope_too(lowered_op_names, scope):
    """``custom_vjp`` rules are traced outside the forward's name stack: the
    chunked attention's backward scan has the scope because the rule gives
    it explicitly."""
    backward = [n for n in _under(lowered_op_names, scope)
                if "transpose(" in n]
    assert backward, scope


@pytest.fixture(scope="module")
def lowered_lm_op_names():
    """Every ``op_name`` in the lowered train step of a pattern decoder
    (models/lm.py) with dropless expert layers, rematerialised blocks."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from metaopt_tpu.models.lm import LMTrial

    hp = dict(hidden_size=32, num_attention_heads=2, num_key_value_heads=1,
              head_dim=16, num_hidden_layers=2, vocab_size=64,
              rope_layout=[0, 1], sliding_window_layout=[0, 1],
              sliding_window_size=8, moe_num_primary_experts=8,
              moe_num_active_primary_experts=2, moe_ffn_hidden_size=16,
              experts_held=(2, 4), remat=True)
    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    trial = LMTrial(hp, mesh=one, n_train=4, batch_size=2, seq_len=16)
    with trial:
        text = trial._step_fn.lower(
            trial.params, trial.opt_state, trial.counts,
            jnp.ones((2, 17), jnp.int32), jax.random.PRNGKey(0),
        ).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("scope", [
    "embed", "attention", "attention.core", "readout_xent", "optimizer",
    "moe", "moe.router", "moe.dispatch", "moe.experts", "moe.combine"])
def test_a_pattern_decoder_s_step_has_ops_under_every_scope(
        lowered_lm_op_names, scope):
    assert _under(lowered_lm_op_names, scope), scope


@pytest.mark.parametrize(
    "scope", ["moe", "moe.router", "moe.dispatch", "moe.experts",
              "moe.combine", "attention.core"])
def test_an_expert_layer_s_backward_ops_carry_its_scopes(
        lowered_lm_op_names, scope):
    backward = [n for n in _under(lowered_lm_op_names, scope)
                if "transpose(" in n]
    assert backward, scope


def test_the_router_s_product_is_under_moe_though_it_runs_before_attention(
        lowered_lm_op_names):
    router = _under(lowered_lm_op_names, "moe.router")
    assert any("dot_general" in n for n in router)
    assert all(_under([n], "moe") for n in router)


def test_the_optimizer_s_ops_are_not_under_a_model_scope(lowered_op_names):
    for name in _under(lowered_op_names, "optimizer"):
        assert not _under([name], "readout_xent") \
            and not _under([name], "attention"), name


# -- the trial ----------------------------------------------------------------


@pytest.mark.parametrize("backend, sp, dropout, train, evaluation", [
    ("tpu", 1, 0.0, "pallas", "pallas"),
    ("tpu", 1, 0.1, "chunked", "pallas"),
    ("cpu", 1, 0.1, "reference", "reference"),
    ("tpu", 2, 0.1, "ring", "ring"),
])
def test_trial_setup_s_span_says_which_attention_route_the_steps_take(
        monkeypatch, capsys, backend, sp, dropout, train, evaluation):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from metaopt_tpu.models.transformer import trial_setup

    mesh = Mesh(np.array(jax.devices()[:sp]).reshape(1, sp, 1),
                ("dp", "sp", "tp"))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.delenv("METAOPT_TPU_SP_IMPL", raising=False)
    trial_setup({"dropout": dropout}, mesh, 1, sp, 1, 100)
    setup = trace.spans("trial.setup")[-1]
    assert setup["attrs"]["attention"] == {
        "dropout": dropout, "train": train, "eval": evaluation}
    # and the reader prints it, a line a trial
    rec = dict(setup, trial="T-9")
    rows = trace.table([rec])
    assert rows[0]["phase"] == "trial.setup"
    trace.print_routes([rec])
    assert capsys.readouterr().out == (
        f"trial T-9: attention {train} in training (dropout {dropout}), "
        f"{evaluation} in evaluation\n")


_NAMES = "attention.out, attention.lse, attention.selected"
_FFN = {"ffn.down": 62_914_560, "ffn.gate": 180_355_072,
        "ffn.up": 180_355_072}        # a block's
_ATT = {"attention.q_proj": 234_881_024, "attention.k_proj": 33_554_432,
        "attention.v_proj": 33_554_432, "attention.out_proj": 167_772_160}
#: the hybrid cell's fourteen candidates (models/lm.py::remat_keeps there)
_HYBRID = {
    **{n: 4 * b for n, b in _FFN.items()},
    **{f"attention.{n}_proj": 31_457_280 for n in "qkv"},
    "attention.out_proj": 62_914_560,
    "linear_attention.q_proj": 70_778_880,
    "linear_attention.k_proj": 70_778_880,
    "linear_attention.v_proj": 141_557_760,
    "linear_attention.g_proj": 141_557_760,
    "linear_attention.a_proj": 1_474_560,
    "linear_attention.b_proj": 1_474_560,
    "linear_attention.out_proj": 188_743_680}


def _over(blocks):
    """The feed-forward's bytes over ``blocks`` blocks: a name's bytes
    stand over all the layers that make it."""
    return {n: blocks * b for n, b in _FFN.items()}


@pytest.mark.parametrize("blocks, keeps, line", [
    (0, None, None),
    (4, None, f"trial T-9: remat: 4 blocks keep {_NAMES}\n"),
    # a harness's own say (models/lm.py::remat_on), as a function of the
    # mesh: a gated feed-forward's products kept, declined in part, declined
    # whole, and on a device that states no limit
    (4, {"keeps": ["attention.out", *_FFN], "bytes": _over(4),
         "room": 2_325_067_032},
     "trial T-9: remat: 4 blocks keep attention.out, ffn.down, ffn.gate, "
     "ffn.up; kept bytes 1.69 GB of room 2.33 GB\n"),
    (8, {"keeps": ["attention.out", "ffn.down"], "bytes": _over(8),
         "room": 2_325_067_032},
     "trial T-9: remat: 8 blocks keep attention.out, ffn.down; kept bytes "
     "0.50 GB of room 2.33 GB; ffn.gate, ffn.up: kept bytes 3.39 GB of room "
     "2.33 GB: not kept\n"),
    (32, {"keeps": ["attention.out"], "bytes": _over(32), "room": 0},
     "trial T-9: remat: 32 blocks keep attention.out; ffn.down, ffn.gate, "
     "ffn.up: kept bytes 13.56 GB of room 0.00 GB: not kept\n"),
    (4, {"keeps": ["attention.out"], "bytes": _over(4), "room": None},
     "trial T-9: remat: 4 blocks keep attention.out; ffn.down, ffn.gate, "
     "ffn.up: kept bytes 1.69 GB of no room stated by the device: not "
     "kept\n"),
    # a model without a gated feed-forward (the 8k MoE decoder's cell): its
    # attention's four projections, kept, and on a device without a limit
    (4, {"keeps": ["attention.out", "attention.out_proj", *list(_ATT)[:3]],
         "bytes": _ATT, "room": 3_202_428_672},
     "trial T-9: remat: 4 blocks keep attention.out, attention.out_proj, "
     "attention.q_proj, attention.k_proj, attention.v_proj; kept bytes 0.47 "
     "GB of room 3.20 GB\n"),
    (4, {"keeps": ["attention.out"], "bytes": _ATT, "room": None},
     "trial T-9: remat: 4 blocks keep attention.out; attention.q_proj, "
     "attention.k_proj, attention.v_proj, attention.out_proj: kept bytes "
     "0.47 GB of no room stated by the device: not kept\n"),
    # one with (the hybrid cell): the linear layers' output projection is
    # the one name that does not fit
    (4, {"keeps": ["linear_attention.states", *[
        n for n in _HYBRID if n != "linear_attention.out_proj"]],
         "bytes": _HYBRID, "room": 2_324_732_464},
     "trial T-9: remat: 4 blocks keep linear_attention.states, "
     + ", ".join(n for n in _HYBRID if n != "linear_attention.out_proj")
     + "; kept bytes 2.28 GB of room 2.32 GB; linear_attention.out_proj: "
     "kept bytes 2.47 GB of room 2.32 GB: not kept\n")])
def test_trial_setup_s_span_says_what_a_rematerialised_block_keeps(
        capsys, blocks, keeps, line):
    """``attrs["remat"]``: the blocks run again in the backward pass and
    the names of ops/attention.REMAT_KEEPS, or what a harness's function of
    the mesh says (the names, each candidate product's bytes, the room);
    absent without remat."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from metaopt_tpu.models.transformer import trial_setup
    from metaopt_tpu.ops.attention import REMAT_KEEPS

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    asked = []
    trial_setup({"dropout": 0.0}, mesh, 1, 1, 1, 100, remat_blocks=blocks,
                **({"remat_keeps": lambda m: asked.append(m) or keeps}
                   if keeps else {}))
    assert asked == ([mesh] if keeps else [])
    setup = trace.spans("trial.setup")[-1]
    assert setup["attrs"].get("remat") == (
        {"blocks": blocks, **(keeps or {"keeps": list(REMAT_KEEPS)})}
        if blocks else None)
    trace.print_routes([dict(setup, trial="T-9")])
    said = capsys.readouterr().out.splitlines(keepends=True)
    assert said[1:] == ([line] if line else [])


@pytest.mark.parametrize("remat, blocks", [(True, 2), (False, None)])
def test_train_and_eval_counts_both_stacks_blocks(remat, blocks):
    from metaopt_tpu.models.transformer import train_and_eval

    hp = dict(d_model=32, n_layers=1, d_ff=64, n_heads=1, vocab=128,
              max_len=16, dropout=0.0, remat=remat)
    train_and_eval(hp, n_train=16, batch_size=8, seq_len=8, steps=1)
    said = trace.spans("trial.setup")[-1]["attrs"].get("remat")
    assert (said and said["blocks"]) == blocks


def test_train_and_eval_leaves_the_trial_s_phases_in_the_ring(tmp_path):
    from metaopt_tpu.models.transformer import train_and_eval

    marker = trace.record("profiler.trace", 0, 0)  # what follows is this test's
    hp = dict(d_model=32, n_layers=1, d_ff=64, n_heads=1, vocab=128,
              max_len=16, dropout=0.0)
    train_and_eval(hp, n_train=16, batch_size=8, seq_len=8, steps=3,
                   save_dir=str(tmp_path / "ckpt"))
    mine = trace.spans()[trace.spans().index(marker) + 1:]
    by_name = {}
    for s in mine:
        by_name.setdefault(s["name"], []).append(s)
    for phase in ("trial.setup", "trial.data", "trial.init", "trial.train"):
        assert len(by_name[phase]) == 1, phase
    assert len(by_name["trial.save"]) == 2  # params, optimizer state
    train = by_name["trial.train"][0]
    for step_span in ("slice_and_shard_batch", "dispatch_step"):
        whole = by_name.get(step_span, [])  # those that compiled
        assert {s["parent"] for s in whole} <= {train["id"]}
        summed = train["attrs"]["per_step"].get(step_span, [0, 0.0])
        assert len(whole) + summed[0] == 3
        assert summed[1] + sum(map(trace.seconds, whole)) \
            <= trace.seconds(train)
    init = by_name["trial.init"][0]
    assert [c["attrs"]["fn"] for c in by_name["compile"]
            if c["parent"] == init["id"]].count("init_fn") == 1
    step_compiles = [c for c in by_name["compile"]
                     if c["attrs"]["fn"] == "train_step"]
    assert len(step_compiles) == 1  # the first dispatch, and no other
    assert step_compiles[0]["parent"] == by_name["dispatch_step"][0]["id"]


def test_profiled_writes_beside_the_spans_inside_a_span(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from metaopt_tpu import client\n"
        "with client.profiled():\n"
        "    jnp.ones(4).sum().block_until_ready()\n")
    info = json.dumps({"id": "trial-9", "experiment": "e"})
    done = _python(code, tmp_path, METAOPT_TPU_TRIAL_INFO=info,
                   JAX_PLATFORMS="cpu",
                   **{trace.PROFILE_DIR_ENV: str(tmp_path / "prof")})
    assert done.returncode == 0, done.stderr
    home = tmp_path / "prof" / "trial-9"
    assert "spans.jsonl" in os.listdir(home)
    assert any(f.endswith(".xplane.pb") for _, _, fs in os.walk(home)
               for f in fs)
    (mark,) = [s for s in _spans_under(home) if s["name"] == "profiler.trace"]
    assert mark["trial"] == "trial-9"
    assert mark["attrs"]["dir"] == str(home)
    assert mark["end_ns"] > mark["start_ns"]


# -- the executor and the worker ---------------------------------------------

_TRIAL_SCRIPT = (
    "from metaopt_tpu import client\n"
    "client.report_objective(1.0)\n")

_ONE_TRIAL = (
    "import sys\n"
    "from metaopt_tpu.executor import SubprocessExecutor\n"
    "from metaopt_tpu.ledger import Trial\n"
    "from metaopt_tpu.space.builder import SpaceBuilder\n"
    "from metaopt_tpu.utils import trace\n"
    "_, template = SpaceBuilder().build(['trial.py', '-x~uniform(0, 1)'])\n"
    "ex = SubprocessExecutor(template, interpreter=[sys.executable],\n"
    "                        poll_interval_s=0.02, profile_dir={prof!r})\n"
    "trace.owner('w0')\n"
    "trial = Trial(params={{'x': 0.5}}, experiment='e')\n"
    "with trace.span('worker.trial', id=trial.id, trial=trial.id):\n"
    "    res = ex.execute(trial)\n"
    "assert res.status == 'completed', res\n"
    "print(trial.id)\n")


@pytest.fixture
def one_profiled_trial(tmp_path):
    """{name: span} of a worker and its one trial, read back from what
    ``profile_dir`` made both processes leave behind."""
    (tmp_path / "trial.py").write_text(_TRIAL_SCRIPT)
    prof = tmp_path / "prof"
    done = _python(_ONE_TRIAL.format(prof=str(prof)), tmp_path)
    assert done.returncode == 0, done.stderr
    trial_id = done.stdout.strip().splitlines()[-1]
    assert sorted(os.listdir(prof)) == sorted([trial_id, "w0"])
    child = {s["name"]: s for s in _spans_under(prof / trial_id)}
    worker = {s["name"]: s for s in _spans_under(prof / "w0")}
    return trial_id, worker, child


def test_the_child_s_start_hangs_under_the_executor_s_spawn(
        one_profiled_trial):
    trial_id, worker, child = one_profiled_trial
    assert set(worker) == {"worker.trial", "executor.spawn", "executor.wait",
                           "executor.collect"}
    assert set(child) >= {"trial.start", "trial.report"}
    spawn, start = worker["executor.spawn"], child["trial.start"]
    assert start["parent"] == spawn["id"]
    assert start["trial"] == spawn["trial"] == trial_id
    assert spawn["parent"] == worker["worker.trial"]["id"] == trial_id
    assert start["pid"] != spawn["pid"]
    # one clock: the child starts inside the spawn and reports inside the wait
    assert spawn["start_ns"] <= start["start_ns"] <= spawn["end_ns"]
    assert start["end_ns"] > spawn["end_ns"]
    # the child's other spans hang under the wait that covers its life
    wait, report = worker["executor.wait"], child["trial.report"]
    assert report["parent"] == wait["id"] and wait["parent"] == trial_id
    assert wait["start_ns"] <= report["end_ns"] <= wait["end_ns"]


def test_the_operator_s_table_splits_a_trial_into_self_times(
        one_profiled_trial, tmp_path, capsys):
    trial_id, worker, child = one_profiled_trial
    rows = {r["phase"]: r for r in trace.table(_spans_under(tmp_path / "prof"))}
    whole = rows["worker.trial"]
    assert whole["trials"] == 1 and whole["median_s"] > 0
    # a root's self time is what none of its children covers
    assert 0 <= whole["self_median_s"] < whole["median_s"] \
        - rows["executor.wait"]["median_s"] + 1e-3
    # the child's start covers the rest of the spawn it began in, and its
    # report a part of the wait
    assert rows["executor.spawn"]["self_median_s"] \
        < rows["executor.spawn"]["median_s"]
    assert rows["executor.wait"]["self_median_s"] \
        < rows["executor.wait"]["median_s"]
    assert rows["trial.start"]["median_s"] < whole["median_s"]
    assert trace.main([str(tmp_path / "prof")]) == 0
    assert "executor.wait" in capsys.readouterr().out


def test_self_time_and_the_hand_off_between_two_trials():
    def rec(name, start, end, id, parent=None, trial="a", pid=1):
        return {"name": name, "start_ns": start, "end_ns": end, "id": id,
                "parent": parent, "trial": trial, "pid": pid, "attrs": {}}

    s = 10 ** 9
    recs = [
        rec("worker.trial", 0, 10 * s, "a"),
        rec("executor.wait", 1 * s, 9 * s, "a.w", "a"),
        rec("trial.train", 2 * s, 6 * s, "a.t", "a.w"),
        rec("trial.eval", 5 * s, 8 * s, "a.e", "a.w"),  # overlaps the train
        rec("worker.trial", 13 * s, 20 * s, "b", trial="b"),
        rec("worker.trial", 11 * s, 12 * s, "c", trial="c", pid=2),
    ]
    # 100 steps summed at the source, and the one that compiled kept whole
    recs[2]["attrs"] = {"per_step": {"dispatch_step": [100, 1.5]}}
    recs += [rec("dispatch_step", 2 * s, 3 * s, "a.d", "a.t"),
             rec("compile", 2 * s, int(2.75 * s), "a.c", "a.d")]
    rows = {r["phase"]: r for r in trace.table(recs)}
    assert rows["executor.wait"]["self_median_s"] == pytest.approx(2.0)
    assert rows["dispatch_step"]["median_s"] == pytest.approx(2.5)
    assert rows["dispatch_step"]["self_median_s"] == pytest.approx(1.75)
    assert rows["trial.train"]["self_median_s"] == pytest.approx(1.5)
    assert rows["worker.trial"]["trials"] == 3
    assert rows["(hand-off)"]["trials"] == 1  # same worker process only
    assert rows["(hand-off)"]["median_s"] == pytest.approx(3.0)


def test_the_reader_names_a_dump_that_is_a_full_ring(tmp_path, capsys):
    line = json.dumps(trace.record("worker.reserve", 1, 2)) + "\n"
    (tmp_path / "w0").mkdir()
    (tmp_path / "w0" / "spans.jsonl").write_text(line * (trace.RING - 1))
    assert len(trace.load(str(tmp_path))) == trace.RING - 1
    assert capsys.readouterr().err == ""
    (tmp_path / "w0" / "spans.jsonl").write_text(line * trace.RING)
    assert len(trace.load(str(tmp_path))) == trace.RING
    assert "full ring" in capsys.readouterr().err


def test_producer_timings_keep_their_keys_and_equal_the_spans_sum():
    from metaopt_tpu.ledger import Experiment, MemoryLedger
    from metaopt_tpu.space import build_space
    from metaopt_tpu.worker import Producer

    from tests.dumbalgo import DumbAlgo

    space = build_space({"x": "uniform(-5, 5)"})
    exp = Experiment("trace-w", MemoryLedger(), space=space, max_trials=5,
                     algorithm={"dumbalgo": {}}, pool_size=2).configure()
    marker = trace.record("profiler.trace", 0, 0)
    prod = Producer(exp, DumbAlgo(space))
    for _ in range(4):
        prod.produce(pool_size=2)
    mine = trace.spans()[trace.spans().index(marker) + 1:]
    total = lambda name: sum(  # noqa: E731
        trace.seconds(s) for s in mine if s["name"] == name)
    assert set(prod.timings) == {"observe_s", "suggest_s", "cycles",
                                 "suggested"}
    assert prod.timings["cycles"] == 4
    assert prod.timings["observe_s"] == pytest.approx(
        total("producer.observe"), rel=1e-9)
    assert prod.timings["suggest_s"] == pytest.approx(
        total("producer.suggest"), rel=1e-9)
    assert prod.timings["observe_s"] > 0 and prod.timings["suggest_s"] > 0
