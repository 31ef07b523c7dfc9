"""Async actor–learner topology tests (ISSUE 4 acceptance contract).

* anchor — ``topology="async"`` with chunk size 1, a full barrier
  (``async_barrier=True``) and ``sync_every = updates_per_iter`` matches
  the bulk-synchronous driver's learner trajectory *bitwise* (params,
  rewards, update counter) for DQN and DDPG — and transitively the fused
  driver via the existing ``num_actors=1, sync_every=1`` parity,
* the double-buffered overlapped mode trains finite with int8 actors,
  records per-sync divergence + actor lag, and honours the
  learner-update staleness contract,
* the double-buffer layout itself: independent slots, host-level swap,
  capacity conservation,
* the pixel (Catch) envs run the conv int8 im2col path under async
  fan-out (fast smoke + slow convergence),
* a 4-device mesh smoke run (slow, subprocess) drives both async
  programs through shard_map.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import actor_learner, dqn, loops
from repro.rl import buffer as rb
from repro.rl.envs import make as make_env
from repro.rl.networks import make_network

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL_DQN = dict(n_envs=4, rollout_steps=4, updates_per_iter=2,
                 buffer_size=512, batch_size=16, warmup=8)
SMALL_DDPG = dict(n_envs=4, rollout_steps=4, updates_per_iter=2,
                  buffer_size=512, batch_size=16, warmup=8)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# anchor: chunk-1 async + full barrier == the bulk-synchronous driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,env,overrides", [
    ("dqn", "cartpole", SMALL_DQN),
    ("ddpg", "pendulum", SMALL_DDPG),
])
def test_async_barrier_anchor_matches_synchronous_driver(algo, env,
                                                         overrides):
    kw = dict(iterations=6, record_every=3, eval_episodes=2, seed=7,
              algo_overrides=dict(overrides))
    sync = loops.train(algo, env, topology="actor-learner", num_actors=1,
                       sync_every=1, **kw)
    anc = loops.train(algo, env, topology="async", num_actors=1,
                      sync_every=overrides["updates_per_iter"],
                      async_barrier=True, steps_per_call=1, **kw)
    for a, b in zip(_leaves(sync.state.params), _leaves(anc.state.params)):
        np.testing.assert_array_equal(a, b)
    assert sync.rewards == anc.rewards
    assert int(sync.state.extras.updates) == int(anc.state.extras.updates)


def test_async_barrier_anchor_with_int8_actors():
    # the int8 snapshot path keeps the contract too (cache packed at the
    # same param values as the sync topology's carried cache)
    kw = dict(iterations=6, record_every=3, eval_episodes=2, seed=11,
              actor_backend="int8", algo_overrides=dict(SMALL_DQN))
    sync = loops.train("dqn", "cartpole", topology="actor-learner",
                       num_actors=1, sync_every=1, **kw)
    anc = loops.train("dqn", "cartpole", topology="async", num_actors=1,
                      sync_every=SMALL_DQN["updates_per_iter"],
                      async_barrier=True, steps_per_call=1, **kw)
    for a, b in zip(_leaves(sync.state.params), _leaves(anc.state.params)):
        np.testing.assert_array_equal(a, b)
    assert sync.rewards == anc.rewards


# ---------------------------------------------------------------------------
# the overlapped double-buffered mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,env,overrides", [
    ("dqn", "cartpole", SMALL_DQN),
    ("ddpg", "pendulum", SMALL_DDPG),
])
def test_async_int8_trains_finite_with_staleness_metrics(algo, env,
                                                         overrides):
    res = loops.train(algo, env, topology="async", num_actors=2,
                      sync_every=4, steps_per_call=2, actor_backend="int8",
                      iterations=8, record_every=4, eval_episodes=2,
                      seed=3, algo_overrides=dict(overrides))
    assert all(np.isfinite(res.rewards))
    # divergence recorded once per true push, per actor, and the int8
    # actors genuinely diverge from the fp32 learner head
    assert len(res.divergences) == len(res.actor_lags) > 0
    assert all(len(d) == 2 for d in res.divergences)
    assert all(np.isfinite(d).all() for d in res.divergences)
    assert any(v > 0 for d in res.divergences for v in d)
    # staleness contract in learner updates: each round dispatches
    # steps_per_call * updates_per_iter = 4 updates, so every retiring
    # snapshot served exactly sync_every = 4 updates
    assert all(lag == 4 for lag in res.actor_lags)
    assert int(res.state.extras.updates) > 0


def test_async_fp32_divergence_is_zero_at_push():
    # a push mints the snapshot from the live learner params — with fp32
    # actors the behaviour head IS the fresh learner head at every sync
    res = loops.train("dqn", "cartpole", topology="async", num_actors=2,
                      sync_every=2, steps_per_call=1, iterations=6,
                      record_every=3, eval_episodes=2, seed=0,
                      algo_overrides=dict(SMALL_DQN))
    assert len(res.divergences) > 0
    assert all(v == 0.0 for d in res.divergences for v in d)


def test_async_learner_consumes_double_buffered_data():
    # data written during one sync period becomes sampleable after the
    # swap: the read slot the final learner state carries must hold
    # transitions, and learner updates must have landed past warmup
    res = loops.train("dqn", "cartpole", topology="async", num_actors=2,
                      sync_every=2, steps_per_call=1, iterations=8,
                      record_every=4, eval_episodes=2, seed=5,
                      algo_overrides=dict(SMALL_DQN))
    read_size = int(rb.replay_total_size(res.state.extras.replay))
    assert read_size > 0
    assert int(res.state.extras.updates) > 0
    # slots are half-capacity: buffer_size / (2 * num_actors) per shard
    assert res.state.extras.replay.data.reward.shape == (2, 128)


def test_async_catch_pixel_smoke():
    # the conv int8 im2col path under async fan-out (fast finiteness
    # smoke; convergence is the slow test below)
    res = loops.train("dqn", "catch", topology="async", num_actors=2,
                      sync_every=4, steps_per_call=2, actor_backend="int8",
                      iterations=4, record_every=2, eval_episodes=2,
                      seed=0, net_kwargs=dict(conv_filters=(4,),
                                              fc_width=16),
                      algo_overrides=dict(SMALL_DQN))
    assert all(np.isfinite(res.rewards))
    assert len(res.divergences) > 0
    assert any(v > 0 for d in res.divergences for v in d)


def test_async_rejects_invalid_configs():
    with pytest.raises(ValueError):
        loops.train("ppo", "cartpole", topology="async", iterations=2)
    # async_barrier is an async-only knob
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", async_barrier=True, iterations=2)
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", topology="actor-learner",
                    async_barrier=True, iterations=2,
                    algo_overrides=dict(SMALL_DQN))
    # batch divisibility (raised by the shared _validate)
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", topology="async", num_actors=3,
                    iterations=2, algo_overrides=dict(SMALL_DQN))
    # double-buffer divisibility: batch divides but
    # buffer_size % (num_actors * 2 slots) != 0 -> init_async refuses
    # rather than silently truncating the slot capacity
    with pytest.raises(ValueError, match="double-buffered"):
        loops.train("dqn", "cartpole", topology="async", num_actors=2,
                    iterations=2,
                    algo_overrides=dict(SMALL_DQN, buffer_size=510))


# ---------------------------------------------------------------------------
# the double-buffer layout
# ---------------------------------------------------------------------------

def test_double_buffer_slots_are_independent():
    db = rb.double_buffer_init(rb.replay_init_sharded, 2, 8, (3,))
    batch = rb.Transition(
        obs=jnp.ones((2, 5, 3)), action=jnp.zeros((2, 5), jnp.int32),
        reward=jnp.ones((2, 5)), done=jnp.zeros((2, 5)),
        next_obs=jnp.ones((2, 5, 3)))
    db = db._replace(write=rb.replay_add_sharded(db.write, batch))
    # writes land in the write slot only
    assert int(rb.replay_total_size(db.write)) == 10
    assert int(rb.replay_total_size(db.read)) == 0
    assert int(rb.double_buffer_total_size(db)) == 10
    # slots never share arrays (the async programs' independence invariant)
    read_ids = {id(x) for x in jax.tree_util.tree_leaves(db.read)}
    write_ids = {id(x) for x in jax.tree_util.tree_leaves(db.write)}
    assert not read_ids & write_ids


def test_double_buffer_swap_is_reference_exchange():
    db = rb.double_buffer_init(rb.replay_init_sharded, 1, 4, (2,))
    batch = rb.Transition(
        obs=jnp.ones((1, 2, 2)), action=jnp.zeros((1, 2), jnp.int32),
        reward=jnp.ones((1, 2)), done=jnp.zeros((1, 2)),
        next_obs=jnp.ones((1, 2, 2)))
    filled = rb.replay_add_sharded(db.write, batch)
    db = db._replace(write=filled)
    swapped = rb.double_buffer_swap(db)
    # the exact objects trade places — no copy, no device op
    assert swapped.read is filled
    assert swapped.write is db.read
    back = rb.double_buffer_swap(swapped)
    assert back.read is db.read and back.write is db.write


# ---------------------------------------------------------------------------
# slow: convergence on pixel Catch + 4-device mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_async_int8_catch_convergence():
    """ISSUE acceptance: async int8 fan-out learns sparse-reward Catch —
    the conv im2col int8 path under true overlapped collection."""
    cfg = dict(n_envs=8, rollout_steps=8, updates_per_iter=4,
               buffer_size=8192, batch_size=32, warmup=256,
               eps_decay_updates=800, target_update_every=100)
    res = loops.train("dqn", "catch", topology="async", num_actors=2,
                      sync_every=16, steps_per_call=4,
                      actor_backend="int8", iterations=800,
                      record_every=100, eval_episodes=16, seed=0,
                      net_kwargs=dict(conv_filters=(8, 8), fc_width=32),
                      algo_overrides=cfg)
    # random play is ~ -5 on [-5, 5]; require clear learning progress
    assert max(res.rewards) > 0.0, res.rewards


@pytest.mark.slow
def test_async_actor_learner_four_device_mesh():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.rl import actor_learner, dqn
        from repro.rl.envs import make as make_env
        from repro.rl.networks import make_network

        env = make_env("cartpole")
        cfg = dqn.DQNConfig(n_envs=4, rollout_steps=4, updates_per_iter=2,
                            buffer_size=1024, batch_size=32, warmup=16,
                            actor_backend="int8", kernel_backend="ref")
        net = make_network(env.spec.obs_shape, env.spec.n_actions)
        al = actor_learner.ActorLearnerConfig(num_actors=4, sync_every=8)
        mesh = jax.make_mesh((4,), ("actor",))
        progs = actor_learner.make_async_actor_learner(
            "dqn", env, net, cfg, al, mesh=mesh)
        learner, wbuf = actor_learner.init_async(
            jax.random.PRNGKey(0), env, net, "dqn", cfg, al)
        env_state, obs = progs.benv_global.reset(jax.random.PRNGKey(1))
        # the carry goes onto the mesh once, under the programs' specs
        learner = actor_learner.place(learner, mesh,
                                      actor_learner.mesh_specs(learner))
        wbuf, env_state, obs = actor_learner.place(
            (wbuf, env_state, obs), mesh, P("actor"))
        snap = progs.make_snapshot(learner)
        key = jax.random.PRNGKey(2)
        chunk, upd = 2, 4
        with jax.sharding.set_mesh(mesh):
            for r in range(4):
                key, k_it = jax.random.split(key)
                k_roll, k_up = jax.random.split(k_it)
                env_state, obs, wbuf, a_m = progs.actor_chunk(
                    snap, env_state, obs, wbuf, k_roll, n_chunks=chunk)
                learner, l_m = progs.learner_chunk(learner, k_up,
                                                   n_updates=upd)
                learner, wbuf = actor_learner.swap_read_slot(learner,
                                                             wbuf)
                snap = progs.make_snapshot(learner)
            div = progs.divergence(learner, snap, obs)
            assert jnp.isfinite(l_m["loss"]), l_m
            assert jnp.isfinite(a_m["reward"]), a_m
        assert div.shape == (4,)
        assert np.isfinite(np.asarray(div)).all()
        # the actor-axis carry stays spread over the mesh
        for leaf in jax.tree_util.tree_leaves((wbuf, env_state, obs)):
            assert len(leaf.sharding.device_set) == 4
        print("ASYNC_MESH_OK", float(l_m["loss"]))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ASYNC_MESH_OK" in out.stdout


# ---------------------------------------------------------------------------
# overlap: the async driver never blocks between records
# ---------------------------------------------------------------------------

def test_async_round_dispatch_returns_futures():
    """The two hot-path programs are dispatchable back-to-back without a
    host sync: after dispatching a full round, every output is a live
    (uncommitted-to-host) jax.Array we can keep feeding forward, and the
    final block resolves the whole pipeline at once."""
    env = make_env("cartpole")
    cfg = dqn.DQNConfig(**dict(SMALL_DQN, actor_backend="int8"))
    net = make_network(env.spec.obs_shape, env.spec.n_actions)
    al = actor_learner.ActorLearnerConfig(num_actors=2, sync_every=4)
    progs = actor_learner.make_async_actor_learner("dqn", env, net, cfg,
                                                   al)
    learner, wbuf = actor_learner.init_async(jax.random.PRNGKey(0), env,
                                             net, "dqn", cfg, al)
    snap = progs.make_snapshot(learner)
    env_state, obs = progs.benv_global.reset(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    for _ in range(3):
        key, k_it = jax.random.split(key)
        k_roll, k_up = jax.random.split(k_it)
        env_state, obs, wbuf, a_m = progs.actor_chunk(
            snap, env_state, obs, wbuf, k_roll, n_chunks=2)
        learner, l_m = progs.learner_chunk(learner, k_up, n_updates=4)
        learner, wbuf = actor_learner.swap_read_slot(learner, wbuf)
        snap = progs.make_snapshot(learner)
    jax.block_until_ready((learner.params, obs))
    assert np.isfinite(float(l_m["loss"]))
    assert np.isfinite(float(a_m["reward"]))
    assert int(rb.replay_total_size(learner.extras.replay)) > 0
