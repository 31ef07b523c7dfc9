"""DQN (Mnih et al. 2013) with target network + replay, QAT-instrumented.

Paper hyperparameters (QuaRL Table 9) are the defaults scaled down:
lr 1e-4, buffer 10k, target update 1000, epsilon 1.0 -> 0.01 over 10% of
training, quantization delay = half of training (quant_delay).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.qconfig import QuantConfig
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.rl import actorq
from repro.rl import buffer as rb
from repro.rl import common
from repro.rl.env import Env, StatefulPolicy, batched_env, rollout
from repro.rl.networks import Network


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    lr: float = 1e-3
    gamma: float = 0.99
    buffer_size: int = 10_000
    batch_size: int = 64
    n_envs: int = 8
    rollout_steps: int = 16       # env steps per iteration (per env)
    updates_per_iter: int = 8
    target_update_every: int = 100  # in gradient updates
    eps_start: float = 1.0
    eps_end: float = 0.01
    eps_decay_updates: int = 4000
    warmup: int = 500             # transitions before learning
    quant: QuantConfig = QuantConfig.none()
    # ActorQ: "int8" computes behaviour-policy Q-values with the packed int8
    # actor (refreshed once per learner update); "int4" halves the cache
    # with byte-packed W4A8 codes; TD learning stays fp32.
    actor_backend: str = "fp32"
    kernel_backend: str = "auto"
    # calib_batch > 0: calibrate static activation scales from that many
    # rollout observations at every cache refresh, replacing the per-layer
    # dynamic range pass and enabling the single-pass fused MLP kernel
    # (rl.actorq.calibrate_actor_cache).  0 keeps dynamic quantization.
    calib_batch: int = 0
    # Replay discipline: "prioritized" samples proportionally to
    # (|td| + eps) ** priority_exponent with IS-weight correction whose
    # exponent anneals is_beta -> 1 over is_beta_anneal_updates learner
    # updates.  priority_exponent=0.0 is bitwise-uniform (static dispatch
    # onto the uniform path — see rl.buffer.use_prioritized).
    replay: str = "uniform"
    priority_exponent: float = 0.6
    is_beta: float = 0.4
    is_beta_anneal_updates: int = 4000


class DQNExtras(NamedTuple):
    target_params: Any
    replay: rb.ReplayState
    updates: jnp.ndarray


def init(key, env: Env, net: Network, cfg: DQNConfig):
    k1, k2 = jax.random.split(key)
    params = net.init(k1)
    opt = adam_init(params, AdamConfig(lr=cfg.lr))
    if rb.use_prioritized(cfg.replay, cfg.priority_exponent):
        replay = rb.per_init(cfg.buffer_size, env.spec.obs_shape)
    else:
        replay = rb.replay_init(cfg.buffer_size, env.spec.obs_shape)
    # target params start equal but must not alias the online buffers:
    # the scan-fused driver donates the whole TrainState, and donation
    # rejects the same buffer appearing twice.
    target = jax.tree_util.tree_map(jnp.array, params)
    return common.TrainState(
        params=params, opt=opt, observers={},
        step=jnp.zeros((), jnp.int32),
        extras=DQNExtras(target_params=target, replay=replay,
                         updates=jnp.zeros((), jnp.int32)))


def _q_values(net, cfg, params, obs, observers, step):
    ctx = common.make_ctx(cfg.quant, observers, step)
    q = net.apply(ctx, params, obs)
    return q, ctx.merged_collection()


def make_behaviour_policy(env: Env, net: Network, cfg: DQNConfig):
    """``build(params, observers, step, updates, qparams=None) ->
    policy(_, obs, key)``.

    The behaviour (data-collection) policy closes over the params it is
    built from — in the fused loop that is the live learner state; in the
    actor–learner topologies (``rl.actor_learner``) it is the actors'
    possibly stale synced copy.  ``actor_backend="int8"`` packs those
    params into the int8 cache once per build (= once per learner update),
    the ActorQ hot path — unless the caller hands in an already-packed
    ``qparams`` cache (the actor–learner topologies carry the cache across
    iterations and repack only at sync points).

    Quantized *sequence* actors (``net.seq_cfg`` set) get an
    ``env.StatefulPolicy`` instead of a plain policy: behaviour Q-values
    come from the incremental int8 KV-cache decode
    (``actorq.quantized_seq_step``) over the per-env cache state that
    ``actorq.maybe_attach_seq_state`` rides inside the batched env state.
    """
    seq_cfg = getattr(net, "seq_cfg", None)

    def build(params, observers, step, updates, qparams=None):
        eps = common.linear_epsilon(updates, cfg.eps_start,
                                    cfg.eps_end, cfg.eps_decay_updates)
        if actorq.is_quantized(cfg.actor_backend):
            # ActorQ hot path: int cache packed once per learner update,
            # reused by every env step of the rollout scan.
            if qparams is None:
                qparams = actorq.pack_actor_params(
                    params, actorq.backend_bits(cfg.actor_backend))

            def behaviour_q(obs):
                return actorq.quantized_apply(qparams, obs,
                                              backend=cfg.kernel_backend)
        else:
            def behaviour_q(obs):
                return _q_values(net, cfg, params, obs, observers, step)[0]

        def select(q, key):
            k_rand, k_explore = jax.random.split(key)
            greedy = jnp.argmax(q, axis=-1)
            rand = jax.random.randint(k_rand, greedy.shape, 0,
                                      env.spec.n_actions)
            explore = jax.random.uniform(k_explore, greedy.shape) < eps
            return jnp.where(explore, rand, greedy).astype(jnp.int32)

        if seq_cfg is not None and actorq.is_quantized(cfg.actor_backend):
            # quantized sequence actor: incremental int8 KV-cache decode
            # over the per-env cache state riding in the env state (see
            # actorq.maybe_attach_seq_state / env.StatefulPolicy)
            def apply(_params, obs, pstate, key):
                q, pstate = actorq.quantized_seq_step(
                    qparams, obs[..., -1, :], pstate,
                    context=seq_cfg.context, backend=cfg.kernel_backend)
                return select(q, key), pstate, q
            return StatefulPolicy(apply)

        def policy(_params, obs, key):
            q = behaviour_q(obs)
            return select(q, key), q
        return policy
    return build


def make_td_update(env: Env, net: Network, cfg: DQNConfig):
    """``td_update(state, batch, replay_size, weights, reduce) ->
    (state, (loss, td_abs))``.

    One fp32 learner step on an already-sampled batch.  ``replay_size``
    gates the warmup; ``weights`` are optional per-transition
    importance-sampling weights (prioritized replay) applied to the Huber
    loss — ``None`` keeps the plain mean, bitwise-identical to the
    pre-PER update; ``reduce`` is applied to gradients/metrics before the
    optimizer (identity on a single host, ``lax.pmean`` over the actor axis
    inside a ``shard_map`` — the data-parallel learner of the actor–learner
    topology).  ``td_abs`` is the per-transition |TD error| (never
    ``reduce``-averaged: in the sharded topology each shard pushes its own
    priorities).  Sampling lives with the caller so the sharded replay of
    ``rl.actor_learner`` and the single fused buffer share this update.
    """
    adam_cfg = AdamConfig(lr=cfg.lr)

    def q_values(params, obs, observers, step):
        return _q_values(net, cfg, params, obs, observers, step)

    def td_update(state: common.TrainState, batch: rb.Transition,
                  replay_size, weights=None, reduce=lambda x: x
                  ) -> Tuple[common.TrainState, Tuple[jnp.ndarray,
                                                      jnp.ndarray]]:
        def loss_fn(params):
            q, new_obs_coll = q_values(params, batch.obs, state.observers,
                                       state.step)
            q_sel = jnp.take_along_axis(
                q, batch.action[:, None].astype(jnp.int32), axis=1)[:, 0]
            q_next, _ = q_values(state.extras.target_params, batch.next_obs,
                                 state.observers, state.step)
            target = batch.reward + cfg.gamma * (1 - batch.done) \
                * jnp.max(q_next, axis=-1)
            td = q_sel - jax.lax.stop_gradient(target)
            if weights is None:
                loss = jnp.mean(common.huber(td))
            else:
                loss = jnp.mean(weights * common.huber(td))
            return loss, (new_obs_coll, jnp.abs(td))

        (loss, (new_coll, td_abs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        grads, loss, new_coll = reduce(grads), reduce(loss), reduce(new_coll)
        new_params, new_opt, _ = adam_update(grads, state.opt, state.params,
                                             adam_cfg)
        updates = state.extras.updates + 1
        do_sync = (updates % cfg.target_update_every) == 0
        target = jax.tree_util.tree_map(
            lambda t, o: jnp.where(do_sync, o, t),
            state.extras.target_params, new_params)
        # learn only after warmup
        warm = replay_size >= cfg.warmup
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(warm, n, o), new_params, state.params)
        state = common.TrainState(
            params=new_params, opt=new_opt, observers=new_coll,
            step=state.step + 1,
            extras=DQNExtras(target, state.extras.replay,
                             jnp.where(warm, updates, state.extras.updates)))
        return state, (loss, td_abs)

    return td_update


def make_iteration(env: Env, net: Network, cfg: DQNConfig):
    actorq.validate_actor_backend(cfg.actor_backend)
    use_per = rb.use_prioritized(cfg.replay, cfg.priority_exponent)
    benv = actorq.maybe_attach_seq_state(
        batched_env(env, cfg.n_envs), net, cfg.actor_backend, cfg.n_envs)
    build_policy = make_behaviour_policy(env, net, cfg)
    td_update = make_td_update(env, net, cfg)

    @jax.jit
    def iteration(state: common.TrainState, env_state, obs, key):
        k_roll, k_updates = jax.random.split(key)
        # the actors' int8 cache, packed from the live params: the fused
        # loop's param push
        with common.phase("param_push"):
            policy_kw = {}
            if actorq.is_quantized(cfg.actor_backend) and cfg.calib_batch:
                # static-requant mode: hand build_policy a cache calibrated on
                # the live observations so the rollout runs the fused kernel
                policy_kw["qparams"] = actorq.make_actor_cache(
                    state.params, cfg.actor_backend,
                    calib_obs=actorq.calib_slice(obs, cfg.calib_batch),
                    backend=cfg.kernel_backend)
            policy = build_policy(state.params, state.observers, state.step,
                                  state.extras.updates, **policy_kw)
        env_state, obs, traj = rollout(
            benv, policy, state.params, env_state, obs, k_roll,
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
                return common.per_learner_step(st, k, cfg, td_update)
            with common.phase("replay_sample"):
                batch = rb.replay_sample(st.extras.replay, k,
                                         cfg.batch_size)
            st, (loss, _) = td_update(st, batch, st.extras.replay.size)
            return st, loss
        state, losses = jax.lax.scan(
            one_update, state, jax.random.split(k_updates,
                                                cfg.updates_per_iter))
        metrics = {"loss": jnp.mean(losses),
                   "reward": jnp.sum(traj.reward) / jnp.maximum(
                       jnp.sum(traj.done), 1.0),
                   "mean_q_var": jnp.var(jax.nn.softmax(
                       traj.logits_or_value, axis=-1), axis=-1).mean()}
        return state, env_state, obs, metrics

    def act_fn(params, obs, observers=None, step=1 << 30):
        ctx = common.make_ctx(cfg.quant, observers or {}, step)
        q = net.apply(ctx, params, obs)
        return jnp.argmax(q, axis=-1).astype(jnp.int32)

    return iteration, act_fn, benv
