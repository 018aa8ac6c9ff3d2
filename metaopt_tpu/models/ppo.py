"""PPO actor-critic — BASELINE config 5 (EvolutionES population search on

PPO/Atari, gang-scheduled slices). Zero-egress stand-in for Atari: a fully
jittable vectorized control environment (noisy double-integrator with a
reward for stabilising at the origin), so rollout + GAE + the clipped PPO
update compile into ONE lax.scan program per trial — no host↔device
round-trip per env step, which is the TPU-idiomatic answer to the reference
era's CPU env loops.

Searchable hparams (the EvolutionES population axes): lr, clip_eps, entropy
coefficient, gae_lambda, hidden width. Fidelity = training iterations.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn


class EnvState(NamedTuple):
    pos: jnp.ndarray   # (n_envs, dim)
    vel: jnp.ndarray   # (n_envs, dim)
    t: jnp.ndarray     # (n_envs,)


DIM = 2
DT = 0.1
HORIZON = 200


def env_reset(key, n_envs: int) -> Tuple[EnvState, jnp.ndarray]:
    kp, kv = jax.random.split(key)
    pos = jax.random.uniform(kp, (n_envs, DIM), minval=-1.0, maxval=1.0)
    vel = jax.random.uniform(kv, (n_envs, DIM), minval=-0.5, maxval=0.5)
    state = EnvState(pos, vel, jnp.zeros(n_envs, jnp.int32))
    return state, obs_of(state)


def obs_of(s: EnvState) -> jnp.ndarray:
    return jnp.concatenate([s.pos, s.vel], axis=-1)  # (n_envs, 2*DIM)


def env_step(
    s: EnvState, action: jnp.ndarray, key
) -> Tuple[EnvState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """action in [-1,1]^DIM accelerates the mass; reward favors the origin."""
    noise = 0.05 * jax.random.normal(key, s.vel.shape)
    vel = 0.98 * s.vel + DT * (jnp.clip(action, -1, 1) + noise)
    pos = s.pos + DT * vel
    t = s.t + 1
    # 0.1 scale keeps discounted returns O(10) so value regression is tame
    reward = -0.1 * (jnp.sum(pos ** 2, -1) + 0.1 * jnp.sum(vel ** 2, -1)
                     + 0.01 * jnp.sum(action ** 2, -1))
    done = (t >= HORIZON) | (jnp.sum(pos ** 2, -1) > 25.0)
    # auto-reset finished envs
    reset_pos = jnp.zeros_like(pos).at[:, 0].set(1.0)
    pos = jnp.where(done[:, None], reset_pos, pos)
    vel = jnp.where(done[:, None], jnp.zeros_like(vel), vel)
    t = jnp.where(done, 0, t)
    return EnvState(pos, vel, t), obs_of(EnvState(pos, vel, t)), reward, done


class ActorCritic(nn.Module):
    """Separate actor/critic trunks — a shared trunk lets the critic's

    large-magnitude regression gradients wreck the policy features.
    """

    hidden: int = 64

    @nn.compact
    def __call__(self, obs):
        x = obs.astype(jnp.float32)
        a = x
        for i in range(2):
            a = jnp.tanh(nn.Dense(self.hidden, name=f"pi_{i}")(a))
        mean = nn.Dense(
            DIM, name="pi_mean", kernel_init=nn.initializers.orthogonal(0.01)
        )(a)
        log_std = self.param("log_std", nn.initializers.constant(-0.5), (DIM,))
        c = x
        for i in range(2):
            c = jnp.tanh(nn.Dense(self.hidden, name=f"v_{i}")(c))
        value = nn.Dense(1, name="v")(c)[..., 0]
        return mean, log_std, value


def train(
    hparams: Dict[str, Any],
    *,
    n_envs: int = 64,
    rollout_len: int = 128,
    iterations: int = 20,
    ppo_epochs: int = 4,
    seed: int = 0,
) -> float:
    """Run PPO; return NEGATIVE mean episode return (HPO minimizes)."""
    # every scalar hyperparameter is a TRACED value, not a baked-in Python
    # constant: all trials of a sweep (same hidden width) then share ONE
    # XLA program, so the persistent compile cache turns a per-trial
    # compile into a per-sweep one
    hp = {
        "clip_eps": jnp.float32(hparams.get("clip_eps", 0.2)),
        "ent_coef": jnp.float32(hparams.get("ent_coef", 0.01)),
        "vf_coef": jnp.float32(hparams.get("vf_coef", 0.5)),
        "gamma": jnp.float32(hparams.get("gamma", 0.99)),
        "lam": jnp.float32(hparams.get("gae_lambda", 0.95)),
    }
    lr = float(hparams.get("lr", 3e-4))
    model = ActorCritic(hidden=int(hparams.get("hidden", 64)))

    key = jax.random.PRNGKey(seed)
    key, k_init, k_env = jax.random.split(key, 3)
    env_state, obs = env_reset(k_env, n_envs)
    params = model.init(k_init, obs)
    # inject_hyperparams carries lr inside opt_state as a traced leaf —
    # the update rule compiles once for any learning rate
    tx = optax.chain(
        optax.clip_by_global_norm(0.5),
        optax.inject_hyperparams(optax.adam)(learning_rate=lr),
    )
    opt_state = tx.init(params)

    def policy_logp(mean, log_std, action):
        var = jnp.exp(2 * log_std)
        return -0.5 * jnp.sum(
            (action - mean) ** 2 / var + 2 * log_std + jnp.log(2 * np.pi), -1
        )

    def rollout(carry, _):
        params, env_state, obs, key = carry
        key, ka, ks = jax.random.split(key, 3)
        mean, log_std, value = model.apply(params, obs)
        action = mean + jnp.exp(log_std) * jax.random.normal(ka, mean.shape)
        logp = policy_logp(mean, log_std, action)
        env_state, next_obs, reward, done = env_step(env_state, action, ks)
        frame = (obs, action, logp, value, reward, done)
        return (params, env_state, next_obs, key), frame

    def gae(values, rewards, dones, last_value, hp):
        def scan_fn(adv, inp):
            v, r, d, v_next = inp
            delta = r + hp["gamma"] * v_next * (1 - d) - v
            adv = delta + hp["gamma"] * hp["lam"] * (1 - d) * adv
            return adv, adv

        v_nexts = jnp.concatenate([values[1:], last_value[None]], 0)
        _, advs = jax.lax.scan(
            scan_fn, jnp.zeros_like(last_value),
            (values, rewards, dones.astype(jnp.float32), v_nexts),
            reverse=True,
        )
        return advs, advs + values

    def ppo_loss(params, batch, hp):
        obs, action, logp_old, adv, ret = batch
        mean, log_std, value = model.apply(params, obs)
        logp = policy_logp(mean, log_std, action)
        ratio = jnp.exp(logp - logp_old)
        adv_n = (adv - adv.mean()) / (adv.std() + 1e-8)
        pg = -jnp.minimum(
            ratio * adv_n,
            jnp.clip(ratio, 1 - hp["clip_eps"], 1 + hp["clip_eps"]) * adv_n,
        ).mean()
        vloss = jnp.mean((value - ret) ** 2)
        entropy = jnp.sum(log_std + 0.5 * jnp.log(2 * np.pi * np.e))
        return pg + hp["vf_coef"] * vloss - hp["ent_coef"] * entropy

    @jax.jit
    def iteration(params, opt_state, env_state, obs, key, hp):
        (params, env_state, obs, key), frames = jax.lax.scan(
            rollout, (params, env_state, obs, key), None, length=rollout_len
        )
        f_obs, f_act, f_logp, f_val, f_rew, f_done = frames
        _, _, last_value = model.apply(params, obs)
        advs, rets = gae(f_val, f_rew, f_done, last_value, hp)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
        batch = (flat(f_obs), flat(f_act), flat(f_logp), flat(advs), flat(rets))

        def epoch(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(ppo_loss)(params, batch, hp)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), _ = jax.lax.scan(
            epoch, (params, opt_state), None, length=ppo_epochs
        )
        mean_reward = f_rew.mean() * HORIZON  # per-episode scale
        return params, opt_state, env_state, obs, key, mean_reward

    mean_return = jnp.asarray(0.0)
    for _ in range(int(iterations)):
        params, opt_state, env_state, obs, key, mean_return = iteration(
            params, opt_state, env_state, obs, key, hp
        )
    return float(-mean_return)


def make_objective(**fixed):
    def objective(params: Dict[str, Any]) -> float:
        kw = dict(fixed)
        if "epochs" in params:
            kw["iterations"] = int(params["epochs"])  # fidelity axis
        return train(params, **kw)

    return objective
