"""Functional environment API (pure JAX — fully jittable/vmappable).

The paper's environments (OpenAI Gym classic control, Atari, PyBullet) are
not installable offline; these are faithful pure-JAX ports of the classic
control dynamics plus a pixel Atari-proxy ("Catch") and an Air-Learning-style
navigation env (see envs/). Everything is:

  env.reset(key)            -> (state, obs)
  env.step(state, action, key) -> (state, obs, reward, done)

with auto-reset handled by ``batched_rollout`` so rollouts are a single
``lax.scan``. Observations are f32; discrete actions int32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.rl import common

State = Any
Obs = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class EnvSpec:
    name: str
    obs_shape: Tuple[int, ...]
    n_actions: int = 0            # discrete envs
    action_dim: int = 0           # continuous envs
    action_scale: float = 1.0     # actor outputs [-1,1] * action_scale
    max_steps: int = 500

    @property
    def continuous(self) -> bool:
        return self.action_dim > 0


class Env(NamedTuple):
    spec: EnvSpec
    reset: Callable[[jax.Array], Tuple[State, Obs]]
    step: Callable[[State, jnp.ndarray, jax.Array],
                   Tuple[State, Obs, jnp.ndarray, jnp.ndarray]]


class StepOut(NamedTuple):
    obs: jnp.ndarray
    action: jnp.ndarray
    reward: jnp.ndarray
    done: jnp.ndarray
    next_obs: jnp.ndarray
    logits_or_value: Any = None


class StatefulPolicy(NamedTuple):
    """A rollout policy that carries per-env recurrent state.

    ``apply(params, obs, pstate, key) -> (action, new_pstate, aux)`` —
    the stateful analogue of the plain ``policy_fn(params, obs, key)``.
    Pair with :func:`attach_policy_state`, which rides ``pstate`` inside
    the env state so every existing driver (rollout scan, shard_map
    topologies, checkpoint/resume) carries, shards, and restores it as
    ordinary env state; ``auto_reset_step`` then resets it per-env to the
    attach-time initial value on episode end, for free.  The int8
    KV-cache transformer actors of ``rl.actorq`` are the consumer.
    """
    apply: Callable[[Any, Obs, Any, jax.Array],
                    Tuple[jnp.ndarray, Any, Any]]


def attach_policy_state(benv: Env, pstate0: Any) -> Env:
    """Wrap a (batched) env so its state is ``(inner_state, pstate)``.

    ``reset`` returns ``pstate0`` (the batched all-reset policy state)
    alongside the inner reset; ``step`` threads ``pstate`` through
    untouched — only :func:`rollout`'s ``StatefulPolicy`` branch writes
    it.  Because ``auto_reset_step`` masks the whole state tree against a
    fresh ``reset`` on done, the policy state of a finished env resets to
    ``pstate0`` with no extra plumbing; likewise checkpointing the env
    state checkpoints the policy state verbatim.
    """
    def reset(key):
        state, obs = benv.reset(key)
        return (state, pstate0), obs

    def step(state, action, key):
        inner, ps = state
        inner, obs, reward, done = benv.step(inner, action, key)
        return (inner, ps), obs, reward, done

    return Env(spec=benv.spec, reset=reset, step=step)


def auto_reset_step(env: Env):
    """step that resets the env when done (state carries the episode)."""
    def step(state, action, key):
        k_step, k_reset = jax.random.split(key)
        new_state, obs, reward, done = env.step(state, action, k_step)
        reset_state, reset_obs = env.reset(k_reset)
        state_out = jax.tree_util.tree_map(
            lambda a, b: jnp.where(_bshape(done, a), a, b),
            reset_state, new_state)
        obs_out = jnp.where(_bshape(done, obs), reset_obs, obs)
        return state_out, obs_out, reward, done
    return step


def _bshape(done, x):
    return done.reshape(done.shape + (1,) * (x.ndim - done.ndim)) \
        if hasattr(x, "ndim") and x.ndim > done.ndim else done


def batched_env(env: Env, n: int) -> Env:
    """vmap an env over a batch dimension."""
    def reset(key):
        return jax.vmap(env.reset)(jax.random.split(key, n))

    def step(state, action, key):
        return jax.vmap(env.step)(state, action, jax.random.split(key, n))

    return Env(spec=env.spec, reset=reset, step=step)


def rollout(env: Env, policy_fn, params, state, obs, key, n_steps: int,
            auto_reset: bool = True):
    """Collect a trajectory with lax.scan.

    policy_fn(params, obs, key) -> (action, aux) — aux is carried into the
    trajectory (logits for exploration analysis, values for A2C/PPO...).
    Returns (final_state, final_obs, StepOut trajectory [n_steps, ...]).
    The policy runs under the ``actor_forward`` phase and the env step
    (with its auto-reset) under ``env_step`` (``common.PHASES``).

    A ``StatefulPolicy`` ``policy_fn`` requires ``env`` to be wrapped
    with :func:`attach_policy_state`: the policy reads and writes the
    ``pstate`` half of the env state each step (the KV-cache actors).
    """
    stepper = auto_reset_step(env) if auto_reset else env.step
    stateful = isinstance(policy_fn, StatefulPolicy)

    def one(carry, key):
        state, obs = carry
        k_act, k_env = jax.random.split(key)
        with common.phase("actor_forward"):
            if stateful:
                inner, ps = state
                action, ps, aux = policy_fn.apply(params, obs, ps, k_act)
                state = (inner, ps)
            else:
                action, aux = policy_fn(params, obs, k_act)
        with common.phase("env_step"):
            state, next_obs, reward, done = stepper(state, action, k_env)
        out = StepOut(obs=obs, action=action, reward=reward, done=done,
                      next_obs=next_obs, logits_or_value=aux)
        return (state, next_obs), out

    (state, obs), traj = jax.lax.scan(one, (state, obs),
                                      jax.random.split(key, n_steps))
    return state, obs, traj


def _build_evaluation(env: Env, act_fn, max_steps: int):
    def one_episode(params, key):
        k_reset, k_run = jax.random.split(key)
        state, obs = env.reset(k_reset)

        def step_fn(carry, k):
            state, obs, done_prev, total = carry
            action = act_fn(params, obs)
            state, obs2, reward, done = env.step(state, action, k)
            total = total + reward * (1.0 - done_prev)
            done_now = jnp.maximum(done_prev, done.astype(jnp.float32))
            return (state, obs2, done_now, total), None

        (_, _, _, total), _ = jax.lax.scan(
            step_fn, (state, obs, jnp.zeros(()), jnp.zeros(())),
            jax.random.split(k_run, max_steps))
        return total

    @jax.jit
    def run(params, keys):
        return jnp.mean(jax.vmap(one_episode, in_axes=(None, 0))(params,
                                                                 keys))

    return run


@functools.lru_cache(maxsize=16)
def _cached_evaluation(env: Env, act_fn, max_steps: int):
    return _build_evaluation(env, act_fn, max_steps)


def evaluate(env: Env, act_fn, params, key, n_episodes: int,
             max_steps: int = 1000) -> jnp.ndarray:
    """Mean undiscounted episode return under a deterministic policy.

    Runs ``n_episodes`` in parallel (one vmap), each until its first done
    (rewards after the first done are masked out).  The whole evaluation
    (reset + rollout scan + masking + mean) compiles to a single XLA
    program, cached per ``(env, act_fn, max_steps)`` — callers that reuse
    one ``act_fn`` object (e.g. the periodic evals in ``loops.train``)
    compile once and dispatch once per eval thereafter.

    ``params`` is any pytree ``act_fn`` understands: fp32 network params,
    fake-quant-simulated params, or the packed int8 ``QuantizedParams`` of
    ``rl.actorq`` (deployment actors run their int8 kernels inside this same
    compiled program).
    """
    try:
        run = _cached_evaluation(env, act_fn, max_steps)
    except TypeError:        # unhashable env/act_fn: build uncached
        run = _build_evaluation(env, act_fn, max_steps)
    return run(params, jax.random.split(key, n_episodes))
