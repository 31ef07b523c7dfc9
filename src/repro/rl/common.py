"""Shared RL plumbing: train-state, QAT context wiring, eval helpers, and
the phase names an iteration's work is traced under."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fake_quant, ptq
from repro.core.qconfig import QuantConfig
from repro.optim.adam import AdamState
from repro.rl import buffer as rb


# The phases of an RL iteration.  Each wraps its work in
# ``jax.named_scope(<phase>)``, which names it in the HLO ``op_name`` of every
# operation traced under it and in a device trace's ``tf_op`` stats; it
# changes nothing else in the compiled program.  Phases nest
# (``replay_sample`` inside ``learner_update``); the innermost one owns an
# operation.
PHASES = ("actor_forward", "env_step", "replay_insert", "replay_sample",
          "learner_update", "param_push")


def phase(name: str):
    """``jax.named_scope`` of one of ``PHASES``."""
    if name not in PHASES:
        raise ValueError(f"phase must be one of {PHASES}, got {name!r}")
    return jax.named_scope(name)


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    observers: Dict[str, fake_quant.ObserverState]
    step: jnp.ndarray
    extras: Any = ()       # algo-specific (target params, noise scale, ...)


def make_ctx(quant: QuantConfig, observers, step):
    return fake_quant.make_context(quant, observers, step)


class PrefixCtx:
    """Namespaces a QAT context (e.g. DDPG actor vs critic observer sites)."""

    def __init__(self, ctx, prefix: str):
        self._ctx = ctx
        self._prefix = prefix

    @property
    def config(self):
        return self._ctx.config

    @property
    def enabled(self):
        # ``enabled`` is a required part of the ctx contract — no fallback.
        return self._ctx.enabled

    def weight(self, name, w):
        return self._ctx.weight(self._prefix + name, w)

    def activation(self, name, x):
        return self._ctx.activation(self._prefix + name, x)

    def merged_collection(self):
        return self._ctx.merged_collection()


def eval_params(params: Any, quant: QuantConfig) -> Any:
    """Apply Algorithm 1/2's evaluation-time quantization to the params.

    PTQ: quantize-dequantize the trained weights.
    QAT: the same fake-quant map with the final (frozen) weight ranges —
    evaluation runs the quantized policy, matching the paper's Eval(Q(M)).
    """
    if quant.is_ptq:
        return ptq.ptq_simulate(params, quant)
    if quant.is_qat:
        def one(path, leaf):
            if (hasattr(leaf, "ndim") and leaf.ndim >= 2
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                from repro.core import affine
                return affine.ptq_tensor(leaf, quant.bits,
                                         axis=leaf.ndim - 1
                                         if leaf.ndim == 4 else None)
            return leaf
        return jax.tree_util.tree_map_with_path(one, params)
    return params


def per_beta(state: TrainState, cfg) -> jnp.ndarray:
    """IS-correction exponent for this learner step.

    Anneals ``cfg.is_beta -> 1`` linearly over ``is_beta_anneal_updates``
    counted on ``state.extras.updates`` — the *learner-update* counter both
    DQN and DDPG carry in their extras, which advances only when an update
    actually lands (warmup steps, whose parameter updates are discarded,
    do not move the schedule).  Counting real updates makes the schedule
    driver-independent: the fused per-step loop, the scan-fused driver
    (``steps_per_call > 1``) and both actor–learner topologies all reach
    ``beta == 1.0`` at exactly ``is_beta_anneal_updates`` learner updates.
    (``state.step``, the unconditional per-call counter, would instead
    anneal on attempted calls — warmup- and chunking-dependent.)
    """
    return linear_epsilon(state.extras.updates, cfg.is_beta, 1.0,
                          cfg.is_beta_anneal_updates)


def per_learner_step(state: TrainState, key, cfg, update_fn):
    """One prioritized learner step on the single (fused) buffer.

    The shared sample -> weighted update -> priority-push protocol used by
    both fused replay algorithms: anneal beta (``per_beta``), draw a
    priority-proportional batch with IS weights, run the algorithm's
    update, and push the refreshed per-transition |TD| back into the
    sum-tree.  (The actor–learner topology runs the same protocol with the
    ``*_sharded`` buffer ops — see ``rl.actor_learner``.)
    """
    beta = per_beta(state, cfg)
    with phase("replay_sample"):
        batch, idx, w = rb.per_sample(state.extras.replay, key,
                                      cfg.batch_size, beta)
    state, (loss, td_abs) = update_fn(
        state, batch, state.extras.replay.replay.size, weights=w)
    with phase("replay_sample"):
        per = rb.per_update_priorities(state.extras.replay, idx, td_abs,
                                       cfg.priority_exponent)
    return state._replace(
        extras=state.extras._replace(replay=per)), loss


def linear_epsilon(step, start: float, end: float, decay_steps: int):
    frac = jnp.clip(step.astype(jnp.float32) / max(decay_steps, 1), 0.0, 1.0)
    return start + frac * (end - start)


def soft_update(target, online, tau: float):
    return jax.tree_util.tree_map(
        lambda t, o: (1 - tau) * t + tau * o, target, online)


def huber(x, delta: float = 1.0):
    a = jnp.abs(x)
    return jnp.where(a <= delta, 0.5 * x * x, delta * (a - 0.5 * delta))
