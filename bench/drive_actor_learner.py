"""The benchmark's one adapter onto the program (``repro``).

Every name the benchmark takes from the program is imported here, so this
file lists the surface a refactor of ``rl/loops.py`` and
``rl/actor_learner.py`` has to keep for the benchmark to run:

* ``rl.actor_learner.init`` builds the carry, ``make_actor_learner(...,
  mesh=)`` the iteration, and on a mesh ``place(state, mesh,
  mesh_specs(state))`` and ``place((env_state, obs), mesh, P("actor"))``
  commit the carry;
* ``rl.loops.make_scan_iteration(iteration, steps_per_call)`` is the
  chunk ``loops.train(topology="actor-learner")`` dispatches: one jitted
  scan with donated ``(state, env_state, obs)``;
* ``rl.dqn.DQNConfig`` and ``optim.adam.AdamConfig`` carry the learner's
  settings, ``configs.quarl_atari`` the paper's widths (the constant a
  configuration's ``policy.constant`` names), ``rl.envs.make`` (with the
  configuration's ``env.kwargs``) and ``rl.networks.make_network`` the
  environment and the policy.

The policy itself is its kind's (``policies/<kind>.py``, bound in
``kinds.Policy``): the kind turns the configuration into
``make_network``'s keyword arguments, checked against that constant, and
maps the benchmark's weights to the program's parameter tree and back
(``to_program``, ``from_program``).  Nothing here names a kind.

Two things here mirror the program rather than call it, and are checked
against it by the benchmark's correctness test on the CPU:

* the learner's sampled replay rows, re-derived from the chunk's key in
  ``sampled_rows`` (``make_scan_iteration`` splits one key per iteration;
  the actor-learner core folds in the device index on a mesh, splits off
  the update keys, one per update and one per local shard; each shard
  draws ``randint(0, size)`` rows);
* the replay layout: iteration ``i`` of the first chunk writes rows
  ``[(i-1) R, i R)`` of every shard, ``R = rollout_steps * n_envs``.
"""
from __future__ import annotations

import copy
import dataclasses
import os
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(_ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(_ROOT, "src"))

from repro.configs import quarl_atari  # noqa: E402
from repro.optim.adam import AdamConfig  # noqa: E402
from repro.rl import actor_learner, dqn, loops  # noqa: E402
from repro.rl.envs import make as make_env  # noqa: E402
from repro.rl.networks import make_network  # noqa: E402

AXIS = "actor"


def network_kwargs(policy) -> Dict:
    """``make_network``'s keyword arguments for a bound policy, checked by
    its kind against the ``quarl_atari`` constant the configuration names
    (None where it names none)."""
    name = policy.config["policy"].get("constant")
    constant = getattr(quarl_atari, name) if name else None
    return policy.kind.network_kwargs(policy.config, constant)


class ActorLearnerDriver:
    """The actor-learner chunk of one cell, built from its configuration
    (bound to its kind, ``kinds.Policy``) and traffic files."""

    def __init__(self, policy, traffic: Dict, mesh=None):
        config = policy.config
        self.policy, self.config = policy, config
        self.traffic, self.mesh = traffic, mesh
        env = make_env(config["env"]["name"],
                       **config["env"].get("kwargs", {}))
        if (list(env.spec.obs_shape) != config["env"]["obs_shape"]
                or env.spec.n_actions != config["env"]["n_actions"]):
            raise ValueError(f"env {env.spec} does not match the "
                             f"configuration file's {config['env']}")
        lrn = config["learner"]
        adam = AdamConfig(lr=lrn["lr"])
        for k in ("b1", "b2", "eps", "grad_clip"):
            if getattr(adam, k) != lrn[k]:
                raise ValueError(f"the program's Adam {k} is "
                                 f"{getattr(adam, k)}, the configuration "
                                 f"file says {lrn[k]}")
        self.env = env
        self.net = make_network(env.spec.obs_shape, env.spec.n_actions,
                                **network_kwargs(policy))
        t = traffic
        if t["topology"] != "actor-learner":
            raise ValueError(f"this adapter drives topology='actor-learner', "
                             f"the traffic file says {t['topology']!r}")
        self.cfg = dataclasses.replace(
            dqn.DQNConfig(), lr=lrn["lr"], gamma=lrn["gamma"],
            target_update_every=lrn["target_update_every"],
            buffer_size=t["buffer_size"], batch_size=t["batch_size"],
            n_envs=t["n_envs"], rollout_steps=t["rollout_steps"],
            updates_per_iter=t["updates_per_iter"],
            eps_start=t["eps"], eps_end=t["eps"], warmup=t["warmup"],
            actor_backend=config["actor_backend"],
            calib_batch=config["calib_batch"], replay=t["replay"])
        self.al = actor_learner.ActorLearnerConfig(
            num_actors=t["num_actors"], sync_every=t["sync_every"])
        iteration, _, self.benv = actor_learner.make_actor_learner(
            "dqn", env, self.net, self.cfg, self.al, mesh=mesh, axis=AXIS)
        self.chunk = loops.make_scan_iteration(iteration,
                                               t["steps_per_call"])
        self.n_dev = mesh.shape[AXIS] if mesh is not None else 1
        self.local_actors = t["num_actors"] // self.n_dev
        self.rows_per_iter = t["rollout_steps"] * t["n_envs"]   # per shard
        self.capacity = t["buffer_size"] // t["num_actors"]     # per shard

    # -- weights ---------------------------------------------------------
    def to_program(self, weights) -> Dict:
        """The benchmark's weights as the program's parameter tree."""
        tree = self.policy.kind.to_program(weights, self.config)
        want = jax.eval_shape(self.net.init, jax.random.PRNGKey(0))
        got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)
        if got != jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want):
            raise ValueError(f"weights {got} do not fit the program's "
                             f"network {want}")
        return tree

    def from_program(self, tree: Dict):
        """The program's parameter tree as the benchmark's weights."""
        return self.policy.kind.from_program(tree, self.config)

    # -- the carry ---------------------------------------------------------
    def init(self, k_weights, k_init, k_env):
        """``(state, env_state, obs)`` in one jitted call: the seed's
        weights (``policy.init(k_weights)``) become the learner's, the
        target's and the actors' parameters (``actor_learner.init`` packs
        the actors' int8 cache from them)."""
        def build(k_weights, k_init, k_env):
            params = self.to_program(self.policy.init(k_weights))
            net = copy.copy(self.net)
            net.init = lambda key, dtype=jnp.float32: params
            state = actor_learner.init(k_init, self.env, net, "dqn",
                                       self.cfg, self.al)
            env_state, obs = self.benv.reset(k_env)
            return state, env_state, obs

        shardings = None
        if self.mesh is not None:
            shapes = jax.eval_shape(build, k_weights, k_init, k_env)
            specs = (actor_learner.mesh_specs(shapes[0], AXIS),
                     jax.tree_util.tree_map(lambda _: P(AXIS), shapes[1]),
                     P(AXIS))
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P))
        state, env_state, obs = jax.jit(build, out_shardings=shardings)(
            k_weights, k_init, k_env)
        if self.mesh is not None:
            state = actor_learner.place(state, self.mesh,
                                        actor_learner.mesh_specs(state,
                                                                 AXIS))
            env_state, obs = actor_learner.place((env_state, obs),
                                                 self.mesh, P(AXIS))
        return state, env_state, obs

    # -- what the check reads ---------------------------------------------
    @staticmethod
    def learner_view(state):
        """``(params, adam m, adam step, replay)`` of the carry."""
        lr = state.learner
        return lr.params, lr.opt.m, lr.opt.step, lr.extras.replay

    @staticmethod
    def losses(metrics):
        """Per-iteration learner loss of a chunk (mean over its updates)."""
        return metrics["loss"]

    def sampled_rows(self, key, n_iters: int):
        """``[(shards, positions, replay_total), ...]``, one per learner
        update of the first ``n_iters`` iterations of a fresh carry, run
        from chunk key ``key``."""
        t = self.traffic
        n_up, la = t["updates_per_iter"], self.local_actors
        per = t["batch_size"] // t["num_actors"]
        out = []
        for it in range(n_iters):
            key, k_it = jax.random.split(key)
            size = min((it + 1) * self.rows_per_iter, self.capacity)
            shards, pos = [[] for _ in range(n_up)], [[] for _ in range(n_up)]
            for d in range(self.n_dev):
                kd = jax.random.fold_in(k_it, d) if self.mesh is not None \
                    else k_it
                _, k_updates = jax.random.split(kd)
                for u, k in enumerate(jax.random.split(k_updates, n_up)):
                    keys_a = k[None] if la == 1 else jax.random.split(k, la)
                    for j in range(la):
                        idx = jax.random.randint(keys_a[j], (per,), 0,
                                                 jnp.int32(max(size, 1)))
                        shards[u].append(np.full(per, d * la + j))
                        pos[u].append(np.asarray(idx))
            total = size * t["num_actors"]
            for u in range(n_up):
                out.append((np.concatenate(shards[u]),
                            np.concatenate(pos[u]), total))
        return out

    def first_rows(self, n_iters: int):
        """Positions each shard holds after the first ``n_iters``
        iterations of a fresh carry."""
        return min(n_iters * self.rows_per_iter, self.capacity)


def gather_rows(replay, shards: np.ndarray, positions: np.ndarray):
    """Host copies of replay rows ``(shard, position)``, read from each
    device's own part of the (possibly sharded) replay."""
    data = replay.data
    fields = {"obs": data.obs, "action": data.action, "reward": data.reward,
              "done": data.done, "next_obs": data.next_obs}
    out = {}
    for name, arr in fields.items():
        rows = [None] * len(shards)
        for part in arr.addressable_shards:
            sl = part.index[0]
            lo = sl.start or 0
            hi = sl.stop if sl.stop is not None else arr.shape[0]
            sel = np.nonzero((shards >= lo) & (shards < hi))[0]
            if sel.size == 0:
                continue
            got = np.asarray(part.data[shards[sel] - lo, positions[sel]])
            for i, r in zip(sel, got):
                rows[i] = r
        out[name] = np.stack(rows)
    return out


def replicas_equal(tree) -> float:
    """Largest absolute difference between any replica of a replicated
    tree and the first one (0.0 on one device)."""
    worst = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        parts = leaf.addressable_shards
        first = parts[0].data
        for part in parts[1:]:
            other = jax.device_put(part.data, first.devices().pop())
            worst = max(worst, float(jnp.max(jnp.abs(other - first))))
    return worst
