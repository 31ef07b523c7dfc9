"""Actor–learner topology tests (ISSUE 2 acceptance contract).

* parity — a single-actor actor–learner run with ``sync_every=1`` is
  bitwise identical to the fused ``loops.train`` driver for DQN (same
  seeds -> same params, same recorded rewards),
* int8 conv compute (tap-major patches through the W8A8 kernel) agrees
  with the fake-quant conv simulation within the ``test_actorq.py``
  tolerance,
* the sharded replay layout round-trips,
* DDPG/PPO rollout collection accepts ``actor_backend="int8"`` and stays
  finite on the smoke envs,
* multi-actor runs populate per-actor divergence metrics and honour the
  ``sync_every`` staleness knob.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import actor_learner, actorq, dqn, loops
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
# parity: 1 actor + sync_every=1 == the fused driver
# ---------------------------------------------------------------------------

def test_single_actor_parity_with_fused_dqn():
    kw = dict(iterations=6, record_every=3, eval_episodes=2, seed=7,
              algo_overrides=dict(SMALL_DQN))
    fused = loops.train("dqn", "cartpole", **kw)
    al = loops.train("dqn", "cartpole", topology="actor-learner",
                     num_actors=1, sync_every=1, **kw)
    for a, b in zip(_leaves(fused.state.params), _leaves(al.state.params)):
        np.testing.assert_array_equal(a, b)
    assert fused.rewards == al.rewards
    # learner extras line up too (target net, update counter)
    for a, b in zip(_leaves(fused.state.extras.target_params),
                    _leaves(al.state.extras.target_params)):
        np.testing.assert_array_equal(a, b)
    assert int(fused.state.extras.updates) == int(al.state.extras.updates)


def test_single_actor_parity_survives_scan_fused_driver():
    kw = dict(iterations=6, record_every=3, eval_episodes=2, seed=11,
              algo_overrides=dict(SMALL_DQN))
    fused = loops.train("dqn", "cartpole", steps_per_call=1, **kw)
    al = loops.train("dqn", "cartpole", topology="actor-learner",
                     num_actors=1, sync_every=1, steps_per_call=3, **kw)
    for a, b in zip(_leaves(fused.state.params), _leaves(al.state.params)):
        np.testing.assert_array_equal(a, b)
    assert fused.rewards == al.rewards


# ---------------------------------------------------------------------------
# multi-actor topology
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,env,overrides", [
    ("dqn", "cartpole", SMALL_DQN),
    ("ddpg", "pendulum", SMALL_DDPG),
])
def test_multi_actor_int8_trains_finite(algo, env, overrides):
    res = loops.train(algo, env, topology="actor-learner", num_actors=2,
                      sync_every=2, actor_backend="int8", iterations=4,
                      record_every=2, eval_episodes=2, seed=3,
                      algo_overrides=dict(overrides))
    assert all(np.isfinite(res.rewards))
    # per-actor divergence recorded at every record point
    assert len(res.divergences) == 2
    assert all(len(d) == 2 for d in res.divergences)
    assert all(np.isfinite(d).all() for d in res.divergences)
    # int8 actors genuinely diverge from the fp32 learner head
    assert any(v > 0 for d in res.divergences for v in d)


def test_sync_every_staleness_contract():
    """Actors keep the stale copy between syncs; a sync point pushes the
    learner's fresh params bitwise."""
    env = make_env("cartpole")
    cfg = dqn.DQNConfig(**dict(SMALL_DQN, warmup=1))
    net = make_network(env.spec.obs_shape, env.spec.n_actions)
    al = actor_learner.ActorLearnerConfig(num_actors=2, sync_every=3)
    state = actor_learner.init(jax.random.PRNGKey(0), env, net, "dqn",
                               cfg, al)
    iteration, _, benv = actor_learner.make_actor_learner(
        "dqn", env, net, cfg, al)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    p0 = _leaves(state.actor_params)
    key = jax.random.PRNGKey(2)
    for t in range(1, 4):
        key, k = jax.random.split(key)
        state, env_state, obs, _ = iteration(state, env_state, obs, k)
        actors = _leaves(state.actor_params)
        learner = _leaves(state.learner.params)
        if t < 3:    # no sync yet: actors still run the init-time params
            for a, b in zip(actors, p0):
                np.testing.assert_array_equal(a, b)
            assert any(not np.array_equal(a, b)
                       for a, b in zip(actors, learner))
        else:        # t == sync_every: fresh learner params pushed bitwise
            for a, b in zip(actors, learner):
                np.testing.assert_array_equal(a, b)


def test_divergence_recorded_only_at_true_pushes():
    """Staleness-contract regression: with ``sync_every=K`` the first real
    param push happens at iteration K — record points before it must NOT
    emit the init-time zero divergence sample (the actors hold a fresh
    copy at t=0 by construction; that is not a sync)."""
    res = loops.train("dqn", "cartpole", topology="actor-learner",
                      num_actors=2, sync_every=4, actor_backend="int8",
                      iterations=8, record_every=2, eval_episodes=2,
                      seed=3, algo_overrides=dict(SMALL_DQN))
    # record points at i = 2, 4, 6, 8; pushes at t = 4, 8 -> the i=2
    # sample (pre-first-push zeros) is skipped
    assert len(res.divergences) == 3
    # every recorded sample comes from a true push of int8-packed params
    assert all(any(v > 0 for v in d) for d in res.divergences)


def test_int8_cache_is_bitwise_stable_between_syncs():
    """Repack-gating regression: the packed int8 actor cache is carried in
    state and repacked under ``lax.cond`` only at sync points — between
    pushes the actor params are unchanged, so the cache must be bitwise
    identical; the sync at t=K repacks from the freshly-pushed params."""
    env = make_env("cartpole")
    cfg = dqn.DQNConfig(**dict(SMALL_DQN, warmup=1, actor_backend="int8"))
    net = make_network(env.spec.obs_shape, env.spec.n_actions)
    al = actor_learner.ActorLearnerConfig(num_actors=2, sync_every=3)
    state = actor_learner.init(jax.random.PRNGKey(0), env, net, "dqn",
                               cfg, al)
    iteration, _, benv = actor_learner.make_actor_learner(
        "dqn", env, net, cfg, al)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    cache0 = _leaves(state.actor_cache)
    key = jax.random.PRNGKey(2)
    for t in range(1, 4):
        key, k = jax.random.split(key)
        state, env_state, obs, _ = iteration(state, env_state, obs, k)
        cache_t = _leaves(state.actor_cache)
        if t < 3:    # no sync yet: the carried cache is bitwise-stable
            for a, b in zip(cache_t, cache0):
                np.testing.assert_array_equal(a, b)
        else:        # t == sync_every: repacked from the pushed params
            assert any(not np.array_equal(a, b)
                       for a, b in zip(cache_t, cache0))
            # and it matches a fresh pack of the synced actor params
            fresh = _leaves(actorq.pack_actor_params(state.actor_params))
            for a, b in zip(cache_t, fresh):
                np.testing.assert_array_equal(a, b)


def test_fp32_divergence_is_pure_staleness():
    # with sync_every=1 and fp32 actors, the behaviour head IS the fresh
    # learner head -> divergence identically zero
    res = loops.train("dqn", "cartpole", topology="actor-learner",
                      num_actors=2, sync_every=1, iterations=4,
                      record_every=2, eval_episodes=2, seed=0,
                      algo_overrides=dict(SMALL_DQN))
    assert all(v == 0.0 for d in res.divergences for v in d)


def test_actor_learner_rejects_on_policy_algos():
    with pytest.raises(ValueError):
        loops.train("ppo", "cartpole", topology="actor-learner",
                    iterations=2)
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", topology="ring", iterations=2)
    # topology knobs are meaningless under the fused driver — loud error
    # instead of silently ignoring them
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", num_actors=4, iterations=2)
    # divisibility contracts surface as ValueError, not bare asserts
    with pytest.raises(ValueError):
        loops.train("dqn", "cartpole", topology="actor-learner",
                    num_actors=3, iterations=2,
                    algo_overrides=dict(SMALL_DQN))


@pytest.mark.parametrize("topology", ["actor-learner", "async"])
def test_train_on_one_device_mesh_places_carry(topology):
    """``loops.train(mesh=...)`` places the carry on the mesh once (so the
    donated chunks alias) and evaluates off it; the returned replay and
    env state stay on the mesh, and the int8 actors' losses are finite."""
    mesh = jax.make_mesh((1,), ("actor",))
    res = loops.train("dqn", "cartpole", topology=topology, num_actors=2,
                      sync_every=2, actor_backend="int8", calib_batch=8,
                      steps_per_call=2, iterations=4, record_every=2,
                      eval_episodes=2, mesh=mesh,
                      algo_overrides=dict(SMALL_DQN))
    assert len(res.losses) == 2 and np.isfinite(res.losses).all()
    for leaf in jax.tree_util.tree_leaves((res.state.extras.replay,
                                           res.env_state)):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
        assert leaf.sharding.spec[0] == "actor"


def test_mesh_specs_shard_only_actor_axis_leaves():
    env = make_env("cartpole")
    cfg = dqn.DQNConfig(**SMALL_DQN)
    net = make_network(env.spec.obs_shape, env.spec.n_actions)
    al = actor_learner.ActorLearnerConfig(num_actors=2)
    state = actor_learner.init(jax.random.PRNGKey(0), env, net, "dqn",
                               cfg, al)
    specs = actor_learner.mesh_specs(state)
    P = jax.sharding.PartitionSpec
    assert all(s == P("actor") for s in jax.tree_util.tree_leaves(
        (specs.learner.extras.replay, specs.divergence),
        is_leaf=lambda s: isinstance(s, P)))
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        (specs.learner.params, specs.actor_params, specs.t),
        is_leaf=lambda s: isinstance(s, P)))


@pytest.mark.slow
def test_actor_learner_eight_device_mesh():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.rl import actor_learner, dqn
        from repro.rl.envs import make as make_env
        from repro.rl.networks import make_network

        env = make_env("cartpole")
        cfg = dqn.DQNConfig(n_envs=4, rollout_steps=4, updates_per_iter=2,
                            buffer_size=1024, batch_size=32, warmup=16,
                            actor_backend="int8", kernel_backend="ref")
        net = make_network(env.spec.obs_shape, env.spec.n_actions)
        al = actor_learner.ActorLearnerConfig(num_actors=8, sync_every=2)
        mesh = jax.make_mesh((8,), ("actor",))
        state = actor_learner.init(jax.random.PRNGKey(0), env, net, "dqn",
                                   cfg, al)
        iteration, act_fn, benv = actor_learner.make_actor_learner(
            "dqn", env, net, cfg, al, mesh=mesh)
        env_state, obs = benv.reset(jax.random.PRNGKey(1))
        key = jax.random.PRNGKey(2)
        with jax.sharding.set_mesh(mesh):
            for i in range(4):
                key, k = jax.random.split(key)
                state, env_state, obs, m = iteration(state, env_state, obs,
                                                     k)
                assert jnp.isfinite(m["loss"]), m
        assert state.divergence.shape == (8,)
        assert np.isfinite(np.asarray(state.divergence)).all()
        print("ACTOR_LEARNER_MESH_OK", float(m["loss"]))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ACTOR_LEARNER_MESH_OK" in out.stdout


# ---------------------------------------------------------------------------
# int8 conv compute (tap-major patches through the W8A8 kernel)
# ---------------------------------------------------------------------------

def _fake_quant_outputs(net, params, obs):
    from repro.core import ptq
    from repro.core.fake_quant import NullQATContext
    from repro.core.qconfig import QuantConfig
    sim = ptq.ptq_simulate(params, QuantConfig.ptq_int(8))
    return net.apply(NullQATContext(), sim, obs)


def test_int8_conv_matches_fake_quant_conv():
    net = make_network((6, 6, 2), 3, conv_filters=(8, 8), fc_width=32)
    params = net.init(jax.random.PRNGKey(2))
    obs = jax.random.normal(jax.random.PRNGKey(3), (5, 6, 6, 2))
    want = _fake_quant_outputs(net, params, obs)
    got = actorq.quantized_apply(actorq.pack_actor_params(params), obs,
                                 backend="ref")
    np.testing.assert_allclose(got, want, atol=1e-2)


def test_int8_conv_interpret_kernel_matches_ref():
    net = make_network((5, 5, 2), 2, conv_filters=(4,), fc_width=16)
    params = net.init(jax.random.PRNGKey(4))
    qp = actorq.pack_actor_params(params)
    obs = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 5, 2))
    ref = actorq.quantized_apply(qp, obs, backend="ref")
    interp = actorq.quantized_apply(qp, obs, backend="interpret")
    np.testing.assert_allclose(interp, ref, rtol=1e-5, atol=1e-5)


def test_int8_conv_unpacked_weights_fall_back_to_fp32():
    # partially-packed trees (fp32 conv leaves) still compute correctly
    layer = {"w": jax.random.normal(jax.random.PRNGKey(0), (3, 3, 2, 4)),
             "b": jnp.zeros((4,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 6, 2))
    y = actorq.int8_conv2d(layer, x.reshape(-1, 2), (6, 6), backend="ref")
    want = jax.nn.relu(jax.lax.conv_general_dilated(
        x, layer["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    np.testing.assert_allclose(y, want.reshape(-1, 4), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# sharded replay
# ---------------------------------------------------------------------------

def _fill(state, key, n, obs_dim=3):
    batch = rb.Transition(
        obs=jax.random.normal(key, (n, obs_dim)),
        action=jnp.arange(n, dtype=jnp.int32),
        reward=jnp.arange(n, dtype=jnp.float32),
        done=jnp.zeros((n,)),
        next_obs=jax.random.normal(key, (n, obs_dim)))
    return rb.replay_add_batch(state, batch), batch


def test_replay_sharding_round_trip():
    shards = []
    for i in range(4):
        s = rb.replay_init(8, (3,))
        s, _ = _fill(s, jax.random.PRNGKey(i), 5)
        shards.append(s)
    stacked = rb.replay_stack(shards)
    assert stacked.size.shape == (4,)
    back = rb.replay_unstack(stacked)
    for orig, got in zip(shards, back):
        for a, b in zip(_leaves(orig), _leaves(got)):
            np.testing.assert_array_equal(a, b)
    assert int(rb.replay_total_size(stacked)) == 4 * 5


def test_sharded_add_matches_independent_shards():
    sharded = rb.replay_init_sharded(2, 8, (3,))
    batch = rb.Transition(
        obs=jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3)),
        action=jnp.stack([jnp.arange(5), 10 + jnp.arange(5)]
                         ).astype(jnp.int32),
        reward=jnp.ones((2, 5)), done=jnp.zeros((2, 5)),
        next_obs=jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3)))
    sharded = rb.replay_add_sharded(sharded, batch)
    for i in range(2):
        solo = rb.replay_init(8, (3,))
        solo = rb.replay_add_batch(
            solo, jax.tree_util.tree_map(lambda x, i=i: x[i], batch))
        got = jax.tree_util.tree_map(lambda x, i=i: x[i], sharded)
        for a, b in zip(_leaves(solo), _leaves(got)):
            np.testing.assert_array_equal(a, b)


def test_sharded_sample_draws_from_own_shard():
    sharded = rb.replay_init_sharded(2, 8, (1,))
    batch = rb.Transition(
        obs=jnp.stack([jnp.zeros((4, 1)), jnp.ones((4, 1))]),
        action=jnp.zeros((2, 4), jnp.int32),
        reward=jnp.stack([jnp.zeros(4), jnp.ones(4)]),
        done=jnp.zeros((2, 4)),
        next_obs=jnp.stack([jnp.zeros((4, 1)), jnp.ones((4, 1))]))
    sharded = rb.replay_add_sharded(sharded, batch)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    out = rb.replay_sample_sharded(sharded, keys, 16)
    assert out.reward.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out.reward[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out.reward[1]), 1.0)


# ---------------------------------------------------------------------------
# DDPG / PPO int8 rollout collection (fused loop)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,env,overrides", [
    ("ddpg", "pendulum", SMALL_DDPG),
    ("ppo", "cartpole", dict(n_envs=4, n_steps=8)),
])
def test_int8_rollout_collection_trains_finite(algo, env, overrides):
    res = loops.train(algo, env, iterations=4, record_every=2,
                      eval_episodes=2, actor_backend="int8",
                      algo_overrides=dict(overrides))
    assert all(np.isfinite(res.rewards))
    assert res.algo_cfg.actor_backend == "int8"


@pytest.mark.slow
@pytest.mark.parametrize("algo,env,overrides,check", [
    # pendulum rewards are large negatives; require clear improvement over
    # the first record (fp32 training follows the same trajectory)
    ("ddpg", "pendulum", dict(n_envs=8, warmup=64),
     lambda r: max(r) > r[0] + 100.0),
    ("ppo", "cartpole", dict(), lambda r: max(r) > 50.0),
])
def test_int8_rollout_collection_converges(algo, env, overrides, check):
    """ISSUE acceptance: int8 rollout collection converges on smoke envs."""
    res = loops.train(algo, env, iterations=120, record_every=40,
                      eval_episodes=8, seed=0, actor_backend="int8",
                      algo_overrides=dict(overrides))
    assert check(res.rewards), res.rewards


# ---------------------------------------------------------------------------
# behaviour-policy builders stay consistent with the fused iteration
# ---------------------------------------------------------------------------

def test_dqn_behaviour_policy_builder_matches_q_head():
    env = make_env("cartpole")
    net = make_network(env.spec.obs_shape, env.spec.n_actions)
    cfg = dqn.DQNConfig(eps_start=0.0, eps_end=0.0)
    params = net.init(jax.random.PRNGKey(0))
    build = dqn.make_behaviour_policy(env, net, cfg)
    policy = build(params, {}, jnp.zeros((), jnp.int32),
                   jnp.zeros((), jnp.int32))
    obs = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    action, q = policy(None, obs, jax.random.PRNGKey(2))
    from repro.rl.common import make_ctx
    from repro.core.qconfig import QuantConfig
    q_want = net.apply(make_ctx(QuantConfig.none(), {}, 0), params, obs)
    np.testing.assert_allclose(q, q_want, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(action),
                                  np.asarray(jnp.argmax(q_want, -1)))
