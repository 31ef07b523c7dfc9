"""How a run decides ``correct``: the timed chunk against the reference.

Set-up drives the fresh carry through the cell's first ``check_iters``
iterations, by the same compiled chunk the window then runs.  Right after
them, ``collect`` reads what they produced while the replay still holds
their rows:

* each iteration's learner loss, Adam's step count, and per leaf the norm
  of Adam's first moment (the clipped gradients as the optimizer got them)
  and of the parameters' change since the seed's weights;
* the replay rows every learner update of those iterations sampled;
* two samples, drawn from the seed, of the transitions the actors
  collected, with the actions the int8 actors chose (exploration is off,
  so each is the int8 head's argmax): one from before their first
  parameter push, where they act on the cache ``actor_learner.init``
  packed from the seed's weights, and one from between the first push and
  the second, where they act on the cache the chunk repacked (and, with
  ``calib_batch``, recalibrated) from the learner's parameters.

Once the window has closed and the program's state is freed, ``judge``
runs the reference (``reference.py``, with the policy kind's forward) over
the same rows from the seed's weights and returns the numbers that
``compare`` holds to the cell's limits:

* ``opt_steps``: Adam steps taken minus updates run (exact);
* ``loss_gap``: worst of the first three iterations' relative loss gap;
* ``grad_gap`` and ``update_gap``: worst leaf's gap between the program's
  and the reference's norm of Adam's first moment and of the parameter
  change, over the larger of that leaf's and the median leaf's reference
  norm.  Leaves whose reference first moment is under a thousandth of the
  median leaf's are left out: under Adam they move by round-off alone;
* ``action_gap``: the widest gap by which the reference's Q-value of an
  action the int8 actors chose before the first push lies below the
  reference's best, over the median of the reference's largest |Q|, with
  the seed's weights;
* ``push_action_gap``: the same for the actions chosen after the first
  push, with the reference's parameters after the updates that push
  carried (``sync_every * updates_per_iter``): a push that is skipped, or
  a cache repacked or calibrated wrongly, reads here;
* ``replica_gap`` (on a mesh): the largest difference between learner
  replicas at the end of the run (exact).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
from drive_actor_learner import gather_rows

LOSS_STEPS = 3
SKIP_BELOW = 1e-3       # of the median leaf's reference first moment


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for x in jax.tree_util.tree_leaves(tree)])


@functools.lru_cache(maxsize=None)
def _program_norms(weights_fn):
    @jax.jit
    def norms(params_list, m_list, k_weights):
        w0 = weights_fn(k_weights)
        dp = jax.tree_util.tree_map(lambda p, q: p - q, params_list, w0)
        leaf = lambda t: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))   # noqa
                                    for x in jax.tree_util.tree_leaves(t)])
        return leaf(m_list), leaf(dp)
    return norms


def collect(driver, state, losses, k_run, k_weights, seed: int,
            traffic: Dict) -> Dict:
    """Read what the set-up's first ``check_iters`` iterations produced
    (see the module docstring); ``losses`` are their chunks' losses."""
    params, m, step, replay = driver.learner_view(state)
    m_norm, dp_norm = _program_norms(driver.policy.init)(
        driver.from_program(params), driver.from_program(m), k_weights)
    batches, sizes = [], []
    for shards, pos, total in driver.sampled_rows(k_run,
                                                  traffic["check_iters"]):
        batches.append(gather_rows(replay, shards, pos))
        sizes.append(total)
    sync, n_rows = traffic["sync_every"], traffic["actor_rows"]
    if (traffic["check_iters"] < 2 * sync or traffic["check_iters"]
            * driver.rows_per_iter > driver.capacity):
        raise ValueError("check_iters has to span two pushes, and the rows "
                         "of its iterations have to stay in the replay")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xAC7])
    shards = rng.integers(0, traffic["num_actors"], n_rows)
    pos = rng.integers(0, driver.first_rows(sync), n_rows)
    acted = gather_rows(replay, shards, pos)
    shards = rng.integers(0, traffic["num_actors"], n_rows)
    pos = rng.integers(driver.first_rows(sync), driver.first_rows(2 * sync),
                       n_rows)
    pushed = gather_rows(replay, shards, pos)
    return {
        "losses": np.concatenate([np.asarray(x, np.float64)
                                  for x in losses]),
        "opt_steps": int(step),
        "m_norm": np.asarray(m_norm, np.float64),
        "dp_norm": np.asarray(dp_norm, np.float64),
        "batches": batches, "sizes": sizes,
        "acted_obs": acted["obs"], "acted_action": acted["action"],
        "pushed_obs": pushed["obs"], "pushed_action": pushed["action"],
    }


def first_iterations(driver, seed: int, traffic: Dict, key_of):
    """A run's set-up up to the check: the seed's fresh carry driven
    through ``check_iters`` iterations by the cell's own chunk, read by
    ``collect``.  Returns ``((state, env_state, obs, key), stash)``."""
    k_weights, k_init, k_env, k_run = (key_of(seed, s) for s in range(4))
    state, env_state, obs = driver.init(k_weights, k_init, k_env)
    key, losses = k_run, []
    for _ in range(traffic["check_iters"] // traffic["steps_per_call"]):
        state, env_state, obs, key, metrics = driver.chunk(state, env_state,
                                                           obs, key)
        losses.append(driver.losses(metrics))
    stash = collect(driver, state, losses, k_run, k_weights, seed,
                    traffic)
    return (state, env_state, obs, key), stash


def _leaf_gap(prog: np.ndarray, refn: np.ndarray, keep: np.ndarray) -> float:
    med = float(np.median(refn[keep])) if keep.any() else 0.0
    denom = np.maximum(refn, med)
    gaps = np.abs(prog - refn) / np.where(denom > 0, denom, 1.0)
    return float(np.max(gaps[keep])) if keep.any() else 0.0


def reference_run(stash: Dict, policy, traffic: Dict, w0,
                  dtype=jnp.float32, batches=None) -> Dict:
    """The reference learner over the stashed rows (or ``batches``): its
    per-iteration losses and, per leaf, the norms of Adam's first moment and
    of the parameter change, in the layout ``collect`` reads the program's
    in, and its parameters as the first push carried them (``pushed``)."""
    hp = dict(policy.config["learner"], warmup=traffic["warmup"])
    batches = stash["batches"] if batches is None else batches
    losses, st, pushed = ref.follow(
        policy.forward, hp, w0, batches, stash["sizes"], dtype=dtype,
        keep_after=traffic["sync_every"] * traffic["updates_per_iter"])
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: x.astype(jnp.float32))
    dp = jax.tree_util.tree_map(lambda p, q: p - q, f32(st.params), w0)
    return {"losses": losses.reshape(-1, traffic["updates_per_iter"])
            .mean(axis=1),
            "m_norm": _leaf_norms(f32(st.m)), "dp_norm": _leaf_norms(dp),
            "opt_steps": int(st.step), "pushed": f32(pushed)}


def learner_numbers(prog: Dict, refr: Dict, n_updates: int
                    ) -> Dict[str, float]:
    """The learner's numbers of ``prog`` (a stash, or a reference run put
    in the program's place) against the reference run ``refr``."""
    keep = refr["m_norm"] >= SKIP_BELOW * np.median(refr["m_norm"])
    n = min(LOSS_STEPS, len(refr["losses"]))
    lp, lr_ = prog["losses"][:n], refr["losses"][:n]
    loss_gap = float(np.max(np.abs(lp - lr_) / np.maximum(np.abs(lr_),
                                                           1e-30)))
    return {
        "opt_steps": float(abs(prog["opt_steps"] - n_updates)),
        "loss_gap": loss_gap,
        "grad_gap": _leaf_gap(prog["m_norm"], refr["m_norm"], keep),
        "update_gap": _leaf_gap(prog["dp_norm"], refr["dp_norm"], keep),
    }


def action_gap(forward, w0, obs: np.ndarray, action: np.ndarray) -> float:
    """Widest gap of a chosen action's reference Q below the best, over
    the median of the largest |Q|."""
    q = ref.q_values(forward, w0, obs)
    best = q.max(axis=1)
    chosen = q[np.arange(len(action)), action.astype(np.int64)]
    scale = float(np.median(np.abs(q).max(axis=1)))
    return float(np.max(best - chosen) / max(scale, 1e-30))


def actor_numbers(forward, w0, pushed, stash: Dict, acted=None,
                  pushed_acted=None) -> Dict[str, float]:
    """``action_gap`` and ``push_action_gap`` of the stashed actions (or
    of ``acted`` and ``pushed_acted`` put in their place), by the
    reference ``forward``."""
    return {
        "action_gap": action_gap(
            forward, w0, stash["acted_obs"],
            stash["acted_action"] if acted is None else acted),
        "push_action_gap": action_gap(
            forward, pushed, stash["pushed_obs"],
            stash["pushed_action"] if pushed_acted is None
            else pushed_acted),
    }


def seed_weights(policy, k_weights):
    """The seed's float32 weights, made on the device in one call."""
    return jax.jit(policy.init)(k_weights)


def judge(stash: Dict, policy, traffic: Dict, k_weights
          ) -> Dict[str, float]:
    """Run the reference over the stashed rows; the cell's numbers."""
    w0 = seed_weights(policy, k_weights)
    refr = reference_run(stash, policy, traffic, w0)
    out = learner_numbers(stash, refr, len(stash["batches"]))
    out.update(actor_numbers(policy.forward, w0, refr["pushed"], stash))
    return out


def compare(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit (an exact number's limit is 0)."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"no reading for {missing}")
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)

