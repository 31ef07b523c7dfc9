"""DDPG (Lillicrap et al. 2015): deterministic actor-critic, replay,
soft target updates, Gaussian exploration noise (modern replacement for the
original OU noise — documented deviation)."""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.qconfig import QuantConfig
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update
from repro.rl import actorq
from repro.rl import buffer as rb
from repro.rl import common
from repro.rl.env import Env, batched_env, rollout
from repro.rl.networks import Network


@dataclasses.dataclass(frozen=True)
class DDPGConfig:
    actor_lr: float = 1e-3
    critic_lr: float = 1e-3
    gamma: float = 0.99
    tau: float = 0.01
    buffer_size: int = 50_000
    batch_size: int = 128
    n_envs: int = 8
    rollout_steps: int = 8
    updates_per_iter: int = 8
    noise_sigma: float = 0.2
    warmup: int = 1000
    quant: QuantConfig = QuantConfig.none()
    # ActorQ: "int8" runs rollout data collection (the exploration policy's
    # mu head) through the packed int8 actor ("int4" = byte-packed W4A8,
    # half the cache); the critic and both gradient paths stay fp32 — the
    # paper's D4PG-style ActorQ split.
    actor_backend: str = "fp32"
    kernel_backend: str = "auto"
    # calib_batch > 0: static activation scales from that many rollout
    # observations at each cache refresh -> single-pass fused MLP kernel
    # (see DQNConfig.calib_batch).  0 keeps dynamic quantization.
    calib_batch: int = 0
    # Replay discipline (see rl.buffer): priorities come from the critic's
    # per-transition |TD error| — the paper's prioritized D4PG analogue.
    # priority_exponent=0.0 is bitwise-uniform (static dispatch).
    replay: str = "uniform"
    priority_exponent: float = 0.6
    is_beta: float = 0.4
    is_beta_anneal_updates: int = 4000


class DDPGExtras(NamedTuple):
    critic_params: Any
    target_actor: Any
    target_critic: Any
    critic_opt: AdamState
    replay: rb.ReplayState
    # learner updates that actually landed (warmup-discarded calls excluded);
    # drives the IS-beta anneal (common.per_beta) and the async staleness
    # accounting — the counter every replay algorithm's extras must carry
    updates: jnp.ndarray


class DDPGNets(NamedTuple):
    actor: Network
    critic: Network


def make_nets(env: Env, hidden=(64, 64)) -> DDPGNets:
    from repro.rl.networks import make_network
    obs_dim = int(jnp.prod(jnp.asarray(env.spec.obs_shape)))
    a_dim = env.spec.action_dim
    actor = make_network(env.spec.obs_shape, a_dim, hidden=hidden)
    critic = make_network((obs_dim + a_dim,), 1, hidden=hidden)
    return DDPGNets(actor, critic)


def init(key, env: Env, nets: DDPGNets, cfg: DDPGConfig):
    k1, k2 = jax.random.split(key)
    actor_params = nets.actor.init(k1)
    critic_params = nets.critic.init(k2)
    opt = adam_init(actor_params, AdamConfig(lr=cfg.actor_lr))
    copt = adam_init(critic_params, AdamConfig(lr=cfg.critic_lr))
    replay_init = rb.per_init \
        if rb.use_prioritized(cfg.replay, cfg.priority_exponent) \
        else rb.replay_init
    replay = replay_init(cfg.buffer_size, env.spec.obs_shape,
                         action_shape=(env.spec.action_dim,),
                         action_dtype=jnp.float32)
    # copies, not aliases: the scan-fused driver donates the TrainState and
    # donation rejects the same buffer appearing twice
    target_actor = jax.tree_util.tree_map(jnp.array, actor_params)
    target_critic = jax.tree_util.tree_map(jnp.array, critic_params)
    return common.TrainState(
        params=actor_params, opt=opt, observers={},
        step=jnp.zeros((), jnp.int32),
        extras=DDPGExtras(critic_params, target_actor, target_critic,
                          copt, replay, jnp.zeros((), jnp.int32)))


def _actor_out(nets, cfg, params, obs, observers, step):
    base = common.make_ctx(cfg.quant, observers, step)
    ctx = common.PrefixCtx(base, "actor/")
    return jnp.tanh(nets.actor.apply(ctx, params, obs)), \
        base.merged_collection()


def make_behaviour_policy(env: Env, nets: DDPGNets, cfg: DDPGConfig):
    """``build(params, observers, step, qparams=None) -> policy(_, obs, key)``.

    Gaussian-noise exploration over the deterministic actor.  With
    ``actor_backend="int8"`` the mu head runs through the packed int8 actor
    (one pack per build = per learner update, or the caller's carried
    ``qparams`` cache — see ``dqn.make_behaviour_policy``); noise/clip/scale
    stay fp32.
    """
    scale = env.spec.action_scale

    def build(params, observers, step, qparams=None):
        if actorq.is_quantized(cfg.actor_backend):
            if qparams is None:
                qparams = actorq.pack_actor_params(
                    params, actorq.backend_bits(cfg.actor_backend))

            def mu_fn(obs):
                mu = actorq.quantized_apply(qparams, obs,
                                            backend=cfg.kernel_backend)
                return jnp.tanh(mu)
        else:
            def mu_fn(obs):
                return _actor_out(nets, cfg, params, obs, observers,
                                  step)[0]

        def policy(_params, obs, k):
            a = mu_fn(obs)
            noise = cfg.noise_sigma * jax.random.normal(k, a.shape)
            return jnp.clip(a + noise, -1.0, 1.0) * scale, a
        return policy
    return build


def make_update(env: Env, nets: DDPGNets, cfg: DDPGConfig):
    """``update(state, batch, replay_size, weights, reduce) ->
    (state, (loss, td_abs))``.

    One critic + actor learner step on an already-sampled batch; ``reduce``
    (identity / ``lax.pmean``) is applied to each gradient before its Adam
    update so the same function serves the fused loop and the data-parallel
    learner of the actor–learner topology.  ``weights`` are optional
    per-transition IS weights (prioritized replay) applied to the *critic*
    loss — the TD-learning half, where the sampling bias matters; the
    actor's deterministic-policy-gradient term stays an unweighted mean
    (standard prioritized-D4PG practice).  ``td_abs`` is the critic's
    per-transition |TD error| (never ``reduce``-averaged — priorities are
    shard-local in the actor–learner topology).
    """
    a_cfg = AdamConfig(lr=cfg.actor_lr)
    c_cfg = AdamConfig(lr=cfg.critic_lr)

    def actor_out(params, obs, observers, step):
        return _actor_out(nets, cfg, params, obs, observers, step)

    def critic_out(params, obs, action, observers, step):
        base = common.make_ctx(cfg.quant, observers, step)
        ctx = common.PrefixCtx(base, "critic/")
        x = jnp.concatenate(
            [obs.reshape(obs.shape[:-len(env.spec.obs_shape)] + (-1,)),
             action], axis=-1)
        return nets.critic.apply(ctx, params, x)[..., 0], \
            base.merged_collection()

    def update(state: common.TrainState, batch: rb.Transition,
               replay_size, weights=None, reduce=lambda x: x):
        ex = state.extras

        def critic_loss(cp):
            next_a, _ = actor_out(ex.target_actor, batch.next_obs,
                                  state.observers, state.step)
            q_next, _ = critic_out(ex.target_critic, batch.next_obs, next_a,
                                   state.observers, state.step)
            target = batch.reward + cfg.gamma * (1 - batch.done) * q_next
            q, new_coll = critic_out(cp, batch.obs, batch.action,
                                     state.observers, state.step)
            td = q - jax.lax.stop_gradient(target)
            if weights is None:
                loss = jnp.mean(jnp.square(td))
            else:
                loss = jnp.mean(weights * jnp.square(td))
            return loss, (new_coll, jnp.abs(td))

        (closs, (new_coll, td_abs)), cgrads = jax.value_and_grad(
            critic_loss, has_aux=True)(ex.critic_params)
        cgrads, closs, new_coll = reduce(cgrads), reduce(closs), \
            reduce(new_coll)
        critic_params, critic_opt, _ = adam_update(
            cgrads, ex.critic_opt, ex.critic_params, c_cfg)

        def actor_loss(ap):
            a, coll2 = actor_out(ap, batch.obs, new_coll, state.step)
            q, _ = critic_out(critic_params, batch.obs,
                              a * env.spec.action_scale, new_coll,
                              state.step)
            return -jnp.mean(q), coll2

        (aloss, new_coll2), agrads = jax.value_and_grad(
            actor_loss, has_aux=True)(state.params)
        agrads, aloss, new_coll2 = reduce(agrads), reduce(aloss), \
            reduce(new_coll2)
        actor_params, actor_opt, _ = adam_update(
            agrads, state.opt, state.params, a_cfg)

        warm = replay_size >= cfg.warmup
        actor_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(warm, n, o), actor_params, state.params)
        critic_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(warm, n, o), critic_params,
            ex.critic_params)

        target_actor = common.soft_update(ex.target_actor, actor_params,
                                          cfg.tau)
        target_critic = common.soft_update(ex.target_critic, critic_params,
                                           cfg.tau)
        state = common.TrainState(
            actor_params, actor_opt, new_coll2, state.step + 1,
            DDPGExtras(critic_params, target_actor, target_critic,
                       critic_opt, ex.replay,
                       jnp.where(warm, ex.updates + 1, ex.updates)))
        return state, (closs + aloss, td_abs)

    return update


def make_iteration(env: Env, nets: DDPGNets, cfg: DDPGConfig):
    actorq.validate_actor_backend(cfg.actor_backend)
    use_per = rb.use_prioritized(cfg.replay, cfg.priority_exponent)
    benv = batched_env(env, cfg.n_envs)
    build_policy = make_behaviour_policy(env, nets, cfg)
    update = make_update(env, nets, cfg)

    @jax.jit
    def iteration(state: common.TrainState, env_state, obs, key):
        k_roll, k_up = jax.random.split(key)
        # the actors' int8 cache, packed from the live params: the fused
        # loop's param push
        with common.phase("param_push"):
            policy_kw = {}
            if actorq.is_quantized(cfg.actor_backend) and cfg.calib_batch:
                # static-requant mode (see dqn.make_iteration)
                policy_kw["qparams"] = actorq.make_actor_cache(
                    state.params, cfg.actor_backend,
                    calib_obs=actorq.calib_slice(obs, cfg.calib_batch),
                    backend=cfg.kernel_backend)
            policy = build_policy(state.params, state.observers, state.step,
                                  **policy_kw)
        env_state, obs, traj = rollout(benv, policy, state.params,
                                       env_state, obs, k_roll,
                                       cfg.rollout_steps)
        with common.phase("replay_insert"):
            flat = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), traj)
            add = rb.per_add if use_per else rb.replay_add_batch
            replay = add(
                state.extras.replay,
                rb.Transition(flat.obs, flat.action, flat.reward, flat.done,
                              flat.next_obs))
        state = state._replace(extras=state.extras._replace(replay=replay))

        @common.phase("learner_update")
        def one_update(st, k):
            if use_per:
                return common.per_learner_step(st, k, cfg, update)
            with common.phase("replay_sample"):
                batch = rb.replay_sample(st.extras.replay, k,
                                         cfg.batch_size)
            st, (loss, _) = update(st, batch, st.extras.replay.size)
            return st, loss
        state, losses = jax.lax.scan(
            one_update, state, jax.random.split(k_up, cfg.updates_per_iter))
        metrics = {"loss": jnp.mean(losses),
                   "reward": jnp.sum(traj.reward) / jnp.maximum(
                       jnp.sum(traj.done), 1.0)}
        return state, env_state, obs, metrics

    def act_fn(params, obs, observers=None, step=1 << 30):
        ctx = common.make_ctx(cfg.quant, observers or {}, step)
        return jnp.tanh(nets.actor.apply(ctx, params, obs)) \
            * env.spec.action_scale

    return iteration, act_fn, benv
