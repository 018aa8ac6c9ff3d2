"""Multi-HOST SPMD evidence: the ring-attention collective path across a
real OS-process boundary.

The pod tests exercise the control plane (ledger/coordinator) across
processes; this one exercises the DATA plane: two `jax.distributed`
processes, 4 virtual CPU devices each, form one 8-device global mesh and
run sequence-parallel ring attention whose `ppermute` ring crosses the
process boundary (the DCN analogue of the ICI ring). Each process checks
its result shards against a locally-computed full reference.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

CHILD = r"""
import os, sys
proc, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=proc)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.ops.ring_attention import ring_attention

devs = jax.devices()
assert len(devs) == 8, f"global device count {len(devs)}"
# 1-axis mesh: the sp ring spans BOTH processes (hops 3->4 and 7->0 cross)
mesh = Mesh(np.array(devs), ("sp",))

B, S, H, D = 2, 64, 2, 8
key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, S, H, D), jnp.float32) / np.sqrt(D)
k = jax.random.normal(kk, (B, S, H, D), jnp.float32)
v = jax.random.normal(kv, (B, S, H, D), jnp.float32)

sharding = NamedSharding(mesh, P(None, "sp", None, None))
qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

out = jax.jit(
    lambda a, b, c: ring_attention(
        a, b, c, mesh=mesh, seq_axis="sp", batch_axis=None, head_axis=None
    )
)(qs, ks, vs)

# local full reference (no sharding)
logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)

for shard in out.addressable_shards:
    sl = shard.index[1]
    np.testing.assert_allclose(
        np.asarray(shard.data), np.asarray(ref[:, sl]), rtol=2e-4, atol=2e-4
    )
print(f"proc {proc} OK: ring attention matched reference on "
      f"{len(out.addressable_shards)} local shards", flush=True)
"""


TRAIN_CHILD = r"""
import os, sys
proc, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=proc)
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.models.data import synthetic_seq2seq
from metaopt_tpu.models.transformer import (
    init_sharded, make_model, make_train_step,
)
from metaopt_tpu.parallel.mesh import use_mesh
from metaopt_tpu.parallel.sharding import shard_batch

devs = jax.devices()
assert len(devs) == 8
# sp is the SLOWEST axis: its two groups are exactly the two processes, so
# the ring-attention ppermute hops cross the process boundary every step
mesh = Mesh(np.array(devs).reshape(2, 2, 2), ("sp", "dp", "tp"))

model = make_model({"d_model": 64, "n_heads": 4, "n_layers": 2,
                    "d_ff": 128, "vocab": 211, "dropout": 0.1})
tx = optax.adamw(1e-3)
batch, seq = 4, 16
with use_mesh(mesh):
    params, opt_state, shardings = init_sharded(model, mesh, tx, (batch, seq))
    step = jax.jit(
        make_train_step(model, tx),
        in_shardings=(shardings[0], shardings[1],
                      NamedSharding(mesh, P("dp")), None),
        out_shardings=(shardings[0], shardings[1], None),
        donate_argnums=(0, 1),
    )
    src, tgt = synthetic_seq2seq(jax.random.PRNGKey(1), batch, seq, model.vocab)
    sharded = shard_batch(mesh, (src, tgt))
    losses = []
    for i in range(3):
        params, opt_state, loss = step(
            params, opt_state, sharded, jax.random.PRNGKey(i)
        )
        losses.append(float(loss))
assert all(l == l and l > 0 for l in losses), losses
assert losses[-1] < losses[0], f"loss must fall over steps: {losses}"
print(f"proc {proc} OK: losses={[round(l, 4) for l in losses]}", flush=True)
"""


def _run_pair(child_src, timeout_s=220):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", child_src, str(i), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"process {i} timed out (distributed init wedged?)")
        outs.append(out)
        assert p.returncode == 0, f"process {i} failed:\n{out}"
    return outs


def test_full_train_step_across_two_processes(tmp_path):
    """The FULL sharded train step (params init, Megatron tp, ring
    attention sp, optimizer update, psum'd loss) over a 2-process global
    mesh — the multi-host training path end-to-end, with the sp ring
    crossing the process boundary."""
    outs = _run_pair(TRAIN_CHILD)
    for i, out in enumerate(outs):
        assert f"proc {i} OK" in out, out
    # the psum'd loss is GLOBAL: both processes must report the same curve
    curve0 = outs[0].splitlines()[-1].split("losses=")[1]
    curve1 = outs[1].splitlines()[-1].split("losses=")[1]
    assert curve0 == curve1


def test_ring_attention_across_two_processes(tmp_path):
    outs = _run_pair(CHILD)
    for i, out in enumerate(outs):
        assert f"proc {i} OK" in out, out


PIPELINE_CHILD = r"""
import os, sys
proc, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=proc)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from metaopt_tpu.parallel.pipeline import pipeline_apply

devs = jax.devices()
assert len(devs) == 8
# pp is the slowest axis: stages 0-3 live on process 0, stages 4-7 on
# process 1, so the stage-to-stage ppermute hop 3->4 (and the interleaved
# schedule's wraparound hop 7->0) cross the process boundary every tick
mesh = Mesh(np.array(devs).reshape(8, 1), ("pp", "dp"))

pp, v, d = 8, 2, 8
kw, kb = jax.random.split(jax.random.PRNGKey(0))
w = jax.random.normal(kw, (pp * v, d, d)) / np.sqrt(d)
b = jax.random.normal(kb, (pp * v, d)) * 0.1
x = jax.random.normal(jax.random.PRNGKey(1), (16, d))


def stage(p, h):
    return jnp.tanh(h @ p[0] + p[1])


y = jax.jit(lambda w, b, x: pipeline_apply(
    stage, (w, b), x, mesh=mesh, n_microbatches=8, virtual_stages=v
))(w, b, x)

ref = x
for i in range(pp * v):
    ref = stage((w[i], b[i]), ref)
np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)
print(f"proc {proc} OK: 16-stage interleaved pipeline matched the "
      "sequential oracle across the process boundary", flush=True)
"""


def test_interleaved_pipeline_across_two_processes(tmp_path):
    """The interleaved virtual-stage pipeline over a 2-process pp=8 mesh:
    both the stage-to-stage hop and the wraparound (virtual-round) hop
    cross the OS-process boundary, and the result still matches the
    16-stage sequential oracle."""
    outs = _run_pair(PIPELINE_CHILD)
    for i, out in enumerate(outs):
        assert f"proc {i} OK" in out, out


ULYSSES_CHILD = r"""
import os, sys
proc, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=proc)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from metaopt_tpu.ops.ulysses import ulysses_attention

devs = jax.devices()
assert len(devs) == 8
# 1-axis sp mesh spanning both processes: the head/sequence all-to-all
# exchanges shards ACROSS the process boundary
mesh = Mesh(np.array(devs), ("sp",))

B, S, H, D = 2, 64, 8, 8
kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(kq, (B, S, H, D), jnp.float32) / np.sqrt(D)
k = jax.random.normal(kk, (B, S, H, D), jnp.float32)
v = jax.random.normal(kv, (B, S, H, D), jnp.float32)

sharding = NamedSharding(mesh, P(None, "sp", None, None))
qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
out = jax.jit(lambda a, b, c: ulysses_attention(
    a, b, c, mesh=mesh, seq_axis="sp", batch_axis=None, head_axis=None
))(qs, ks, vs)

logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)
for shard in out.addressable_shards:
    sl = shard.index[1]
    np.testing.assert_allclose(
        np.asarray(shard.data), np.asarray(ref[:, sl]), rtol=2e-4, atol=2e-4
    )
print(f"proc {proc} OK: ulysses all-to-all matched reference on "
      f"{len(out.addressable_shards)} local shards", flush=True)
"""


def test_ulysses_across_two_processes(tmp_path):
    outs = _run_pair(ULYSSES_CHILD)
    for i, out in enumerate(outs):
        assert f"proc {i} OK" in out, out


CONTROL_CHILD = r"""
import os, sys
proc, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=proc)
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from metaopt_tpu.parallel.control import run_signaled

devs = jax.devices()
assert len(devs) == 8
mesh = Mesh(np.array(devs).reshape(2, 4), ("pp", "dp"))

# ONLY process 0 ever sees the signal (the coordinator-polling host);
# process 1's local flag is always False. The mesh collective must make
# BOTH processes stop at the same chunk boundary — a unilateral exit
# would hang the other process in pod_agree's own all-reduce.
state = {"n": 0}
def step(c):
    state["n"] += 1
    return c + 1

def should_stop():
    return proc == 0 and state["n"] >= 6

carry, steps, stopped = run_signaled(
    step, 0, mesh=mesh, should_stop=should_stop,
    max_steps=100, check_every=4,
)
assert stopped and steps == 8, (steps, stopped)
print(f"proc {proc} OK: stopped together at step {steps}", flush=True)
"""


def test_pod_coherent_early_stop_across_two_processes(tmp_path):
    """The ICI-style control plane: a stop signal visible to one host is
    agreed over the mesh so the whole gang leaves the step loop at the
    same step (north star: early-stop broadcast as a mesh collective)."""
    outs = _run_pair(CONTROL_CHILD)
    for i, out in enumerate(outs):
        assert f"proc {i} OK: stopped together at step 8" in out, out
