"""Plain reference of the benchmarked DQN learner.

Written from the published description (Mnih et al. 2015 DQN) in
straightforward ``jax.numpy``.  It imports nothing of the program under
test and takes nothing the program made: the weights come from the
policy kind's ``init_weights`` and the benchmark's seed, the transitions
are the rows the learner was fed.

The network is the policy kind's (``policies/<kind>.py``): every function
here takes its ``forward(weights, obs)``, Q-values ``(B, n_actions)``, and
works on its weights as a pytree.  ``init_layers`` and ``dense_chain`` are
plain layers that kinds may build on.

The learner step is the one the configuration states: Huber TD loss
(delta 1) against a target network, mean over the batch; gradients
clipped to global norm ``grad_clip``; Adam with bias correction.  The
optimizer state advances on every update; the parameters move only once
the replay holds ``warmup`` transitions, and the warm-update counter that
schedules the target network counts only those updates.

``dtype=float32`` runs every matmul at ``highest`` precision;
``dtype=bfloat16`` is the control: parameters, activations, gradients and
optimizer state all in bfloat16.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def init_layers(key, shapes: Sequence[tuple], dtype=jnp.float32) -> List:
    """A list of layers ``{"w": ..., "b": ...}`` of the weight ``shapes``:
    N(0, 1/fan_in) weights, zero biases, one key per layer."""
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, shape in zip(keys, shapes):
        fan_in = int(np.prod(shape[:-1]))
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        out.append({"w": w.astype(dtype),
                    "b": jnp.zeros((shape[-1],), dtype)})
    return out


def dense_chain(layers: Sequence[Dict], x):
    """``x @ w + b`` through ``layers``, ReLU on every layer but the
    last; ``x`` is flattened to ``(B, -1)`` before each."""
    last = len(layers) - 1
    for i, p in enumerate(layers):
        x = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
        if i < last:
            x = jax.nn.relu(x)
    return x


def huber(x):
    """Huber loss with delta 1."""
    a = jnp.abs(x)
    return jnp.where(a <= 1.0, 0.5 * x * x, a - 0.5)


class LearnerState(NamedTuple):
    """The reference learner's state: parameters, target, Adam."""

    params: list
    target: list
    m: list
    v: list
    step: jnp.ndarray       # optimizer steps taken
    updates: jnp.ndarray    # warm updates taken


def learner_init(weights) -> LearnerState:
    """Fresh Adam state; the target starts as the weights."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return LearnerState(weights, weights, zeros,
                        jax.tree_util.tree_map(jnp.zeros_like, weights),
                        jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))


def make_step(forward: Callable, hp: Dict, dtype):
    """``step(state, batch, replay_size) -> (state, loss)``, jitted."""
    lr, gamma = hp["lr"], hp["gamma"]
    b1, b2, eps, clip = hp["b1"], hp["b2"], hp["eps"], hp["grad_clip"]

    def loss_fn(params, target, batch):
        obs = batch["obs"].astype(dtype)
        q = forward(params, obs)
        q_sel = jnp.take_along_axis(q, batch["action"][:, None], axis=1)[:, 0]
        q_next = forward(target, batch["next_obs"].astype(dtype))
        y = batch["reward"].astype(dtype) + gamma * (
            1 - batch["done"].astype(dtype)) * jnp.max(q_next, axis=-1)
        return jnp.mean(huber(q_sel - jax.lax.stop_gradient(y)))

    @jax.jit
    def step(st: LearnerState, batch, replay_size):
        loss, g = jax.value_and_grad(loss_fn)(st.params, st.target, batch)
        leaves = jax.tree_util.tree_leaves(g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in leaves))
        factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        g = jax.tree_util.tree_map(lambda x: (x * factor).astype(dtype), g)
        t = st.step + 1
        bc1 = (1.0 - b1 ** t.astype(jnp.float32)).astype(dtype)
        bc2 = (1.0 - b2 ** t.astype(jnp.float32)).astype(dtype)
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                   st.m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                   st.v, g)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)),
            st.params, m, v)
        warm = replay_size >= hp["warmup"]
        updates = st.updates + 1
        target = jax.tree_util.tree_map(
            lambda t_, n: jnp.where(updates % hp["target_update_every"] == 0,
                                    n, t_), st.target, new)
        params = jax.tree_util.tree_map(lambda n, o: jnp.where(warm, n, o),
                                        new, st.params)
        return LearnerState(params, target, m, v, t,
                            jnp.where(warm, updates, st.updates)), loss

    return step


@functools.lru_cache(maxsize=None)
def _jitted_forward(forward: Callable):
    return jax.jit(forward)


def follow(forward: Callable, hp: Dict, weights, batches, sizes,
           dtype=jnp.float32, keep_after: int = 0):
    """Run the learner over ``batches`` (dicts of host arrays, one per
    update) with the replay sizes the program saw.  Returns
    ``(losses, state, kept)``: ``kept`` is the parameters after the first
    ``keep_after`` updates (None where that is 0)."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        w = jax.tree_util.tree_map(lambda x: x.astype(dtype), weights)
        st = learner_init(w)
        step = make_step(forward, hp, dtype)
        losses, kept = [], None
        for i, (batch, size) in enumerate(zip(batches, sizes)):
            st, loss = step(st, batch, jnp.int32(size))
            losses.append(loss)
            if i + 1 == keep_after:
                kept = st.params
        return np.asarray(jnp.stack(losses), np.float64), st, kept


def q_values(forward: Callable, weights, obs, block: int = 512):
    """Reference Q-values at ``highest`` precision, in blocks of rows."""
    fwd = _jitted_forward(forward)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, obs.shape[0], block):
            out.append(np.asarray(fwd(weights, obs[i:i + block])))
    return np.concatenate(out)
