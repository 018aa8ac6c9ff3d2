"""Model-zoo smoke tests: tiny shapes, CPU mesh, loss sanity.

These validate the BASELINE-config surfaces (objective callables, fidelity
plumbing, sharded train steps) — performance is chipbench's job, on the
chip.
"""

import jax
import numpy as np
import pytest

from metaopt_tpu.models import objectives


class TestObjectives:
    def test_rosenbrock_minimum(self):
        assert objectives.rosenbrock({"x": 1.0, "y": 1.0}) == 0.0
        assert objectives.rosenbrock({"x": 0.0, "y": 0.0}) == 1.0

    def test_make_objective(self):
        fn = objectives.make_objective("sphere")
        assert fn({"a": 3.0, "b": 4.0}) == 25.0


class TestMLP:
    def test_train_and_eval_learns(self):
        from metaopt_tpu.models.mlp import train_and_eval

        err = train_and_eval(
            {"lr": 1e-3, "width": 64, "depth": 2, "dropout": 0.0},
            n_train=512, n_val=256, batch_size=64, epochs=2,
        )
        assert 0.0 <= err < 0.9  # teacher task is learnable → beats chance-ish

    def test_objective_fidelity_plumbing(self):
        from metaopt_tpu.models.mlp import make_objective

        obj = make_objective(n_train=256, n_val=128, batch_size=64)
        err = obj({"lr": 1e-3, "width": 32, "depth": 1, "dropout": 0.0,
                   "epochs": 1})
        assert 0.0 <= err <= 1.0


class TestResNet:
    def test_tiny_resnet_trains(self):
        from metaopt_tpu.models.resnet import train_and_eval

        err = train_and_eval(
            {"lr": 0.05, "depth": 18, "batch_size": 32},
            n_train=128, n_val=64, epochs=1, hw=16,
        )
        assert 0.0 <= err <= 1.0

    def test_resnet50_param_count(self):
        """Depth-50 builds the real bottleneck architecture (~23.5M params)."""
        import jax.numpy as jnp
        from metaopt_tpu.models.resnet import ResNet

        model = ResNet(depth=50)
        vars_ = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
            )
        )
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(vars_["params"]))
        assert 23e6 < n < 26e6


class TestTransformer:
    def test_sharded_train_step_runs(self):
        from metaopt_tpu.models.transformer import train_and_eval
        from metaopt_tpu.parallel import make_mesh

        mesh = make_mesh([("dp", 4), ("tp", 2)])
        loss = train_and_eval(
            {"d_model": 64, "n_heads": 4, "n_layers": 2, "d_ff": 128,
             "vocab": 97, "lr": 1e-3, "dropout": 0.0},
            mesh=mesh, n_train=64, batch_size=16, seq_len=12, steps=3,
        )
        assert np.isfinite(loss) and loss > 0

    def test_flash_routed_under_tp_mesh(self, monkeypatch):
        """tp>1 no longer bypasses the kernel: the chunked flash path (plus
        attention-weight dropout) trains under a dp×tp mesh via shard_map."""
        import jax
        from metaopt_tpu.models.transformer import train_and_eval
        from metaopt_tpu.parallel import make_mesh

        # what a TPU's training steps take at this dropout rate
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        mesh = make_mesh([("dp", 2), ("tp", 4)])
        loss = train_and_eval(
            {"d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
             "vocab": 97, "lr": 1e-3, "dropout": 0.1},
            mesh=mesh, n_train=32, batch_size=8, seq_len=12, steps=2,
        )
        assert np.isfinite(loss) and loss > 0

    def test_ring_attention_under_sp_mesh(self):
        """sp>1 routes MHA through ring attention; numerics match the
        single-device model on the same params."""
        import jax
        import jax.numpy as jnp
        from metaopt_tpu.models.transformer import make_model
        from metaopt_tpu.parallel import make_mesh
        from metaopt_tpu.parallel.mesh import use_mesh

        model = make_model({"d_model": 32, "n_heads": 2, "n_layers": 1,
                            "d_ff": 64, "vocab": 50, "dropout": 0.0})
        src = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 49 + 1
        params = model.init(jax.random.PRNGKey(0), src, src, train=False)
        plain = model.apply(params, src, src, train=False)
        mesh = make_mesh([("dp", 2), ("sp", 2), ("tp", 2)])
        with use_mesh(mesh):
            ringed = model.apply(params, src, src, train=False)
        np.testing.assert_allclose(
            np.asarray(ringed, np.float32), np.asarray(plain, np.float32),
            atol=0.25, rtol=0.05,  # bf16 model, different reduce orders:
            # logits are O(30), bf16 has ~3 significant digits
        )

    def test_sp_indivisible_seq_raises(self):
        """sp>1 with a non-divisible sequence must error, never silently
        replicate attention over the sp axis."""
        import jax
        import jax.numpy as jnp
        from metaopt_tpu.models.transformer import make_model
        from metaopt_tpu.parallel import make_mesh
        from metaopt_tpu.parallel.mesh import use_mesh
        import pytest

        model = make_model({"d_model": 32, "n_heads": 2, "n_layers": 1,
                            "d_ff": 64, "vocab": 50, "dropout": 0.0})
        src = jnp.ones((2, 15), jnp.int32)  # 15 % sp(2) != 0
        params = model.init(jax.random.PRNGKey(0), src, src, train=False)
        mesh = make_mesh([("dp", 4), ("sp", 2)])
        with use_mesh(mesh), pytest.raises(ValueError, match="multiples"):
            model.apply(params, src, src, train=False)

    def test_blocked_xent_routing_explicit_shards_vs_mesh(self):
        """The xent-routing predicate honors an explicit ``shards`` count
        and, with the default, reads the ambient mesh — out-of-mesh the
        tensor is treated as unsharded."""
        from metaopt_tpu.models.transformer import blocked_xent_enabled
        from metaopt_tpu.parallel import make_mesh
        from metaopt_tpu.parallel.mesh import use_mesh

        # global f32 logits = 4*64*512*50000 ≈ 6.55 GB: over the 4 GiB
        # gate unsharded, under it when split 4 ways over dp
        batch, seq, vocab = 64, 512, 50_000
        assert blocked_xent_enabled(batch, seq, vocab)  # no ambient mesh
        assert not blocked_xent_enabled(batch, seq, vocab, shards=4)
        mesh = make_mesh([("dp", 4), ("tp", 2)])
        with use_mesh(mesh):
            # ambient routing divides by dp*sp (tp does not shard (B, T))
            assert not blocked_xent_enabled(batch, seq, vocab)
            # explicit shards overrides the ambient mesh both directions
            assert blocked_xent_enabled(batch, seq, vocab, shards=1)
            assert not blocked_xent_enabled(batch, seq, vocab, shards=8)

    def test_sp_train_step_runs(self):
        from metaopt_tpu.models.transformer import train_and_eval

        loss = train_and_eval(
            {"d_model": 32, "n_heads": 4, "n_layers": 1, "d_ff": 64,
             "vocab": 97, "lr": 1e-3, "dropout": 0.1},
            tp=2, sp=2, n_train=32, batch_size=8, seq_len=16, steps=2,
        )
        assert np.isfinite(loss) and loss > 0

    def test_attention_dropout_active_in_train(self):
        """Two train-mode applies with different dropout keys differ; eval
        mode is deterministic (attention-weight dropout is live)."""
        import jax
        import jax.numpy as jnp
        from metaopt_tpu.models.transformer import make_model

        model = make_model({"d_model": 32, "n_heads": 2, "n_layers": 1,
                            "d_ff": 64, "vocab": 50, "dropout": 0.3})
        src = jnp.ones((2, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), src, src, train=False)
        a = model.apply(params, src, src, train=True,
                        rngs={"dropout": jax.random.PRNGKey(1)})
        b = model.apply(params, src, src, train=True,
                        rngs={"dropout": jax.random.PRNGKey(2)})
        assert not np.allclose(np.asarray(a), np.asarray(b))
        c = model.apply(params, src, src, train=False)
        d = model.apply(params, src, src, train=False)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d))

    def test_tp_kernels_actually_sharded(self):
        import jax.numpy as jnp
        import optax
        from flax import linen as nn
        from jax.sharding import PartitionSpec as P
        from metaopt_tpu.models.transformer import init_sharded, make_model
        from metaopt_tpu.parallel import make_mesh

        mesh = make_mesh([("dp", 2), ("tp", 4)])
        model = make_model({"d_model": 32, "n_heads": 4, "n_layers": 1,
                            "d_ff": 64, "vocab": 53})
        tx = optax.adam(1e-3)
        params, _, shardings = init_sharded(model, mesh, tx, (8, 10))
        wi = params["enc0"]["mlp"]["wi"]["kernel"]
        assert nn.meta.unbox(wi).sharding.spec == P(None, "tp")
        q = params["enc0"]["self_attn"]["q"]["kernel"]
        assert nn.meta.unbox(q).sharding.spec == P(None, "tp", None)

    def test_max_len_forwarded_and_overflow_is_loud(self):
        """make_model must forward max_len (the 2026-08-01 TPU bench lost
        its seq-1024 stages to the 512 default), and a sequence longer than
        the positional table must raise at trace time, not as an XLA
        broadcast error."""
        import jax
        import jax.numpy as jnp
        import pytest
        from metaopt_tpu.models.transformer import make_model

        h = {"d_model": 32, "n_heads": 2, "n_layers": 1, "d_ff": 64,
             "vocab": 50, "dropout": 0.0}
        short = make_model(h)  # default table: 512
        src = jnp.ones((2, 513), jnp.int32)
        with pytest.raises(ValueError, match="max_len"):
            short.init(jax.random.PRNGKey(0), src, src, train=False)
        long = make_model({**h, "max_len": 1024})
        assert long.max_len == 1024
        long.init(jax.random.PRNGKey(0), src, src, train=False)


class TestPPO:
    def test_ppo_improves_return(self):
        from metaopt_tpu.models.ppo import train

        bad = train({"lr": 1e-3}, n_envs=32, rollout_len=64, iterations=2)
        good = train({"lr": 1e-3}, n_envs=32, rollout_len=64, iterations=30)
        assert np.isfinite(bad) and np.isfinite(good)
        assert good < bad  # more training → higher return → lower objective
        assert good < 5.0  # and the control problem is actually solved

    def test_objective_fidelity(self):
        from metaopt_tpu.models.ppo import make_objective

        obj = make_objective(n_envs=8, rollout_len=16)
        v = obj({"lr": 1e-3, "epochs": 2})
        assert np.isfinite(v)

    def test_trials_share_one_compiled_program(self, tmp_path):
        """Different (lr, clip_eps, ent_coef, gae_lambda) trials must hit
        the SAME persistent-cache entries: hyperparameters are traced
        values, not baked-in constants. Proven across real processes: the
        second trial must add ZERO new entries to the compile cache the
        first trial populated (a recompile would store a new program)."""
        import os
        import subprocess
        import sys

        cache = str(tmp_path / "xla-cache")
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            JAX_COMPILATION_CACHE_DIR=cache,
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        )
        code = (
            "from metaopt_tpu.models.ppo import train;"
            "print(train({{'lr': {lr}, 'clip_eps': {ce}, 'ent_coef': {ec},"
            "'gae_lambda': {gl}}}, iterations=1, n_envs=8, rollout_len=8,"
            "ppo_epochs=2))"
        )
        def run(**hp):
            subprocess.check_call([sys.executable, "-c", code.format(**hp)],
                                  env=env, stdout=subprocess.DEVNULL)
            return len(os.listdir(cache))

        n1 = run(lr=1e-3, ce=0.1, ec=0.01, gl=0.9)
        n2 = run(lr=4e-4, ce=0.3, ec=0.05, gl=0.99)
        assert n1 > 0
        assert n2 == n1, "second PPO trial compiled new programs"


class TestTrialCheckpoint:
    def test_orbax_roundtrip_preserves_sharded_state(self, tmp_path):
        import jax
        import numpy as np
        import optax

        from metaopt_tpu.models.checkpoint import (
            has_state, restore_state, save_state,
        )
        from metaopt_tpu.models.transformer import init_sharded, make_model
        from metaopt_tpu.parallel.mesh import make_mesh, use_mesh

        mesh = make_mesh([("dp", 4), ("tp", 2)])  # the 8 virtual devices
        model = make_model({"d_model": 32, "n_heads": 2, "n_layers": 1,
                            "d_ff": 64, "vocab": 101, "dropout": 0.0})
        tx = optax.adamw(1e-3)
        with use_mesh(mesh):
            params, opt_state, shardings = init_sharded(model, mesh, tx, (8, 8))
        path = str(tmp_path / "ck")
        assert not has_state(path)
        save_state(path + "/params", params)
        save_state(path + "/opt_state", opt_state)
        assert has_state(path)

        with use_mesh(mesh):
            params2, opt_state2, shardings2 = init_sharded(
                model, mesh, tx, (8, 8), seed=7,  # different init
            )
            restored = restore_state(path + "/params", params2, shardings2[0])
            ropt = restore_state(path + "/opt_state", opt_state2, shardings2[1])
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert b.sharding.is_equivalent_to(a.sharding, a.ndim)
        assert jax.tree.structure(ropt) == jax.tree.structure(opt_state)

    def test_train_and_eval_resumes_from_checkpoint(self, tmp_path):
        from metaopt_tpu.models.transformer import train_and_eval

        hp = {"d_model": 32, "n_heads": 2, "n_layers": 1, "d_ff": 64,
              "vocab": 101, "dropout": 0.0, "lr": 1e-2, "warmup": 2}
        first = str(tmp_path / "first")
        loss1 = train_and_eval(hp, steps=6, n_train=64, batch_size=8,
                               seq_len=8, save_dir=first)
        # continuing from the checkpoint starts BELOW the cold first loss
        loss2 = train_and_eval(hp, steps=6, n_train=64, batch_size=8,
                               seq_len=8, restore_dir=first)
        assert loss2 < loss1


class TestFullParallelComposition:
    def test_tp_sp_ep_in_one_jit(self):
        """Megatron tp + ring-attention sp + expert-parallel ep compose in
        a single jitted train step (the dryrun's step D, pinned here)."""
        import jax
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from metaopt_tpu.models.data import synthetic_seq2seq
        from metaopt_tpu.models.transformer import (
            init_sharded, make_model, make_train_step,
        )
        from metaopt_tpu.parallel.mesh import make_mesh, use_mesh
        from metaopt_tpu.parallel.sharding import shard_batch

        mesh = make_mesh([("dp", 1), ("tp", 2), ("sp", 2), ("ep", 2)])
        model = make_model({"d_model": 64, "n_heads": 4, "n_layers": 2,
                            "d_ff": 128, "vocab": 211, "dropout": 0.1,
                            "n_experts": 2})
        tx = optax.adamw(1e-3)
        with use_mesh(mesh):
            params, opt_state, sh = init_sharded(model, mesh, tx, (2, 16))
            step = jax.jit(
                make_train_step(model, tx),
                in_shardings=(sh[0], sh[1],
                              NamedSharding(mesh, P("dp")), None),
                out_shardings=(sh[0], sh[1], None),
                donate_argnums=(0, 1),
            )
            src, tgt = synthetic_seq2seq(jax.random.PRNGKey(1), 2, 16,
                                         model.vocab)
            batch = shard_batch(mesh, (src, tgt))
            losses = []
            for i in range(2):
                params, opt_state, loss = step(
                    params, opt_state, batch, jax.random.PRNGKey(i)
                )
                losses.append(float(loss))
        assert all(l == l and l > 0 for l in losses)
        assert losses[1] < losses[0]  # it actually trains


class TestRemat:
    def test_remat_matches_plain_forward_and_trains(self):
        import jax
        import jax.numpy as jnp
        import optax
        from metaopt_tpu.models.transformer import (
            loss_fn, make_model,
        )

        h = {"d_model": 32, "n_heads": 2, "n_layers": 2, "d_ff": 64,
             "vocab": 61, "dropout": 0.0}
        plain = make_model(h)
        remat = make_model({**h, "remat": True})
        src = jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) % 60 + 1
        params = plain.init(jax.random.PRNGKey(0), src, src, train=False)
        # identical parameter structure: remat is a pure recompute schedule
        y0 = plain.apply(params, src, src, train=False)
        y1 = remat.apply(params, src, src, train=False)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=1e-5, rtol=1e-5)
        # and gradients flow through the rematted backward
        g = jax.grad(lambda p: loss_fn(
            remat, p, (src, src), jax.random.PRNGKey(1)
        ))(params["params"])
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(g))
