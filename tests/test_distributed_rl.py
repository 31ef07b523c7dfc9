"""shard_map data-parallel RL: single-device degenerate path inline; the
8-device path runs in a subprocess (device count is locked at jax init)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.rl import a2c, distributed
    from repro.rl.envs import make as make_env
    from repro.rl.networks import make_network

    env = make_env("cartpole")
    cfg = a2c.A2CConfig(n_envs=16, n_steps=8, actor_backend=BACKEND)
    net = make_network(env.spec.obs_shape, env.spec.n_actions + 1)
    mesh = jax.make_mesh((8,), ("data",))
    state = a2c.init(jax.random.PRNGKey(0), env, net, cfg)
    iteration, act_fn, benv = distributed.make_distributed_a2c(
        env, net, cfg, mesh)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    with jax.sharding.set_mesh(mesh):
        for i in range(5):
            key, k = jax.random.split(key)
            state, env_state, obs, m = iteration(state, env_state, obs, k)
            assert jnp.isfinite(m["loss"]), m
    print("DISTRIBUTED_OK", float(m["loss"]))
""")


def test_distributed_a2c_one_device():
    """Degenerate mesh (1 device): shard_map path == plain data parallel."""
    from repro.rl import a2c, distributed
    from repro.rl.envs import make as make_env
    from repro.rl.networks import make_network

    env = make_env("cartpole")
    cfg = a2c.A2CConfig(n_envs=8, n_steps=8)
    net = make_network(env.spec.obs_shape, env.spec.n_actions + 1)
    mesh = jax.make_mesh((1,), ("data",))
    state = a2c.init(jax.random.PRNGKey(0), env, net, cfg)
    iteration, act_fn, benv = distributed.make_distributed_a2c(
        env, net, cfg, mesh)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    with jax.sharding.set_mesh(mesh):
        for i in range(3):
            state, env_state, obs, m = iteration(
                state, env_state, obs, jax.random.PRNGKey(10 + i))
    assert np.isfinite(float(m["loss"]))
    assert int(state.step) == 3


def test_distributed_a2c_int8_actor_one_device():
    """ActorQ inside the shard_map rollout (degenerate 1-device mesh)."""
    from repro.rl import a2c, distributed
    from repro.rl.envs import make as make_env
    from repro.rl.networks import make_network

    env = make_env("cartpole")
    cfg = a2c.A2CConfig(n_envs=8, n_steps=8, actor_backend="int8",
                        kernel_backend="ref")
    net = make_network(env.spec.obs_shape, env.spec.n_actions + 1)
    mesh = jax.make_mesh((1,), ("data",))
    state = a2c.init(jax.random.PRNGKey(0), env, net, cfg)
    iteration, act_fn, benv = distributed.make_distributed_a2c(
        env, net, cfg, mesh)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    with jax.sharding.set_mesh(mesh):
        for i in range(3):
            state, env_state, obs, m = iteration(
                state, env_state, obs, jax.random.PRNGKey(10 + i))
    assert np.isfinite(float(m["loss"]))
    assert int(state.step) == 3


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["fp32", "int8"])
def test_distributed_a2c_eight_devices(backend):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    script = f"BACKEND = {backend!r}\n" + SCRIPT
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DISTRIBUTED_OK" in out.stdout
