"""Distributed RL training: shard_map data-parallel rollouts + learners.

The paper's QuaRL experiments ran on single GPUs; scaling the study (its
"fast and environmentally sustainable" pitch) means running many environment
batches in parallel. This module maps the A2C iteration onto a 'data' mesh
axis with ``jax.shard_map``:

  * every device steps its own slice of the vectorized environments and
    computes gradients on its own rollouts (params replicated),
  * gradients are ``psum``-averaged across the axis,
  * all devices apply the identical Adam update (replicated optimizer state),

— i.e. synchronous data-parallel actor-learners, the standard A2C scaling
pattern, QAT-instrumented exactly like the single-host path (observer
updates are EMA states; they are pmean-ed so every replica keeps identical
ranges).

Works on any mesh whose 'data' axis divides n_envs; on a 1-device CPU mesh it
degenerates to the single-host path (used by the fast tests; the multi-device
path is exercised with 8 fake host devices in tests/test_distributed_rl.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.adam import AdamConfig, adam_update
from repro.rl import a2c, actorq, common
from repro.rl.env import Env, batched_env, rollout
from repro.rl.networks import Network


def make_distributed_a2c(env: Env, net: Network, cfg: a2c.A2CConfig,
                         mesh: Mesh, axis: str = "data"):
    """Returns (iteration, act_fn, benv_global) — iteration signature matches
    the single-host a2c.make_iteration.

    ``cfg.actor_backend="int8"`` runs the ActorQ rollout inside the
    shard_map: every device packs the replicated params into an int8 cache
    once per learner update and steps its local env slice through the W8A8
    kernel; gradients (learner side) stay fp32 and are psum-averaged as
    usual.
    """
    actorq.validate_actor_backend(cfg.actor_backend)
    n_dev = mesh.shape[axis]
    assert cfg.n_envs % n_dev == 0, (cfg.n_envs, n_dev)
    local_envs = cfg.n_envs // n_dev
    benv_local = batched_env(env, local_envs)
    benv_global = batched_env(env, cfg.n_envs)
    adam_cfg = AdamConfig(lr=cfg.lr)
    n_act = env.spec.n_actions
    int8_policy = actorq.make_sampling_policy(
        env.spec, backend=cfg.kernel_backend) \
        if actorq.is_quantized(cfg.actor_backend) else None

    def heads(params, obs, observers, step):
        ctx = common.make_ctx(cfg.quant, observers, step)
        out = net.apply(ctx, params, obs)
        return out[..., :n_act], out[..., n_act], ctx.merged_collection()

    def shard_fn(state: common.TrainState, env_state, obs, key):
        # per-device: local rollout + local grads
        key = jax.random.fold_in(key[0], jax.lax.axis_index(axis))

        if int8_policy is not None:
            # quantized actor inside the shard: one int pack per update,
            # shared by all local env steps (params are replicated, so every
            # device packs the identical cache; calib_batch calibrates per
            # shard from its local obs slice -> fused kernel in the shard)
            qparams = actorq.make_actor_cache(
                state.params, cfg.actor_backend,
                calib_obs=actorq.calib_slice(obs, cfg.calib_batch)
                if cfg.calib_batch else None,
                backend=cfg.kernel_backend)

            def policy(params, obs, k):
                return int8_policy(qparams, obs, k)
        else:
            def policy(params, obs, k):
                logits, _, _ = heads(params, obs, state.observers,
                                     state.step)
                return jax.random.categorical(k, logits).astype(jnp.int32), \
                    logits

        k_roll, _ = jax.random.split(key)
        env_state, last_obs, traj = rollout(
            benv_local, policy, state.params, env_state, obs, k_roll,
            cfg.n_steps)

        def loss_fn(params):
            logits, values, new_coll = heads(params, traj.obs,
                                             state.observers, state.step)
            _, last_value, _ = heads(params, last_obs, state.observers,
                                     state.step)

            def disc(carry, step_t):
                reward, done = step_t
                carry = reward + cfg.gamma * carry * (1 - done)
                return carry, carry
            _, returns = jax.lax.scan(
                disc, jax.lax.stop_gradient(last_value),
                (traj.reward, traj.done), reverse=True)
            adv = jax.lax.stop_gradient(returns) - values
            logp = jax.nn.log_softmax(logits, axis=-1)
            logp_a = jnp.take_along_axis(logp, traj.action[..., None],
                                         axis=-1)[..., 0]
            p = jax.nn.softmax(logits, axis=-1)
            entropy = -jnp.sum(p * logp, axis=-1).mean()
            pg = -(jax.lax.stop_gradient(adv) * logp_a).mean()
            v_loss = jnp.square(adv).mean()
            return (pg + cfg.value_coef * v_loss
                    - cfg.entropy_coef * entropy), new_coll

        (loss, new_coll), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        # synchronous data parallelism: average grads (and observer EMA
        # states + scalar metrics) across the axis
        grads = jax.lax.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        new_coll = jax.lax.pmean(new_coll, axis)
        reward = jax.lax.pmean(
            jnp.sum(traj.reward) / jnp.maximum(jnp.sum(traj.done), 1.0),
            axis)

        new_params, new_opt, _ = adam_update(grads, state.opt, state.params,
                                             adam_cfg)
        new_state = common.TrainState(new_params, new_opt, new_coll,
                                      state.step + 1, ())
        return new_state, env_state, last_obs, {"loss": loss,
                                                "reward": reward}

    sharded = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(axis), P(axis), P()), check_vma=False)

    @jax.jit
    def iteration(state, env_state, obs, key):
        keys = jax.random.split(key, n_dev)
        return sharded(state, env_state, obs, keys)

    def act_fn(params, obs, observers=None, step=1 << 30):
        ctx = common.make_ctx(cfg.quant, observers or {}, step)
        out = net.apply(ctx, params, obs)
        return jnp.argmax(out[..., :n_act], axis=-1).astype(jnp.int32)

    return iteration, act_fn, benv_global
