"""Bring-up check: the ActorQ main path end to end on a TPU.

Drives the library entry points that README's Quickstart uses
(``repro.rl.loops.train``, ``repro.serving.PolicyServer``) at the paper's
policy widths (``repro.configs.quarl_atari``), with seeded random weights
and a few training iterations each:

  (a) device   the first device is a TPU, and ``kernel_backend="auto"``
               resolves to the Pallas kernels;
  (b) conv     DQN on catch, 4 int8 actors, actor-learner topology, the
               ATARI_DQN conv net (im2col through ``int8_matmul``);
  (c) mlp      DQN on cartpole with DEPLOY_POLICY_II and calibrated
               (fused-kernel) int8 and int4 actors, in the actor-learner
               and async topologies;
  (d) parity   the Pallas forward of (b)'s and (c)'s caches, and of a
               DEPLOY_POLICY_III cache at batch 1 and 256, matches the
               ``ref`` oracle (docs/contracts.md tolerance, identical
               greedy actions); each compiled actor step holds a
               ``tpu_custom_call``;
  (e) serving  a PolicyServer on (c)'s int8 cache answers 512 sessions
               for a few steps across one hot-swap, every action equal to
               the ``ref`` backend's;
  (f) output   each phase's compile, cold and warm seconds, one line each.

``--four-chips`` runs only the sharded actor path: actor-learner and async
DQN over ``jax.make_mesh((4,), ("actor",))`` against the same
configuration on one chip, in one process.

Usage (from the repository root, on a machine with a TPU):

    python chip_smoke.py
    python chip_smoke.py --four-chips

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Any failure exits non-zero, and so
does a run with no TPU attached.  Times are bring-up readings, not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# How far each training run is cut: the widths are the paper's, the depth of
# the run is a few iterations.
ITERATIONS = 4
STEPS_PER_CALL = 2
EVAL_EPISODES = 4
NUM_ACTORS = 4
CALIB_BATCH = 64
DQN = dict(n_envs=8, rollout_steps=16, updates_per_iter=4,
           buffer_size=8192, batch_size=64, warmup=256)
SESSIONS = 512
SERVE_STEPS = 4
# docs/contracts.md: Pallas kernels match the ref oracle to 1e-5
RTOL = ATOL = 1e-5

# JAX's monitoring events for a backend compile and a persistent-cache hit
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Backend-compile seconds and persistent-cache hits in this process."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def run_phase(counter, name, fn):
    """Run ``fn`` twice (cold, then warm), print its times, return the
    cold run's result."""
    c0, h0 = counter.compile_s, counter.cache_hits
    t0 = time.perf_counter()
    out = fn()
    cold = time.perf_counter() - t0
    compile_s, hits = counter.compile_s - c0, counter.cache_hits - h0
    t0 = time.perf_counter()
    fn()
    warm = time.perf_counter() - t0
    print(f"phase {name}: passed; compile {compile_s:.2f} s "
          f"(persistent-cache hits {hits}), cold {cold:.2f} s, "
          f"warm {warm:.2f} s", flush=True)
    return out


def check_device(min_devices=1):
    """Phase (a): fail unless a TPU is attached and ``auto`` is Pallas."""
    import jax
    from repro.kernels import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU attached (jax.devices()[0] "
                         f"is {devs[0].platform!r})")
    if len(devs) < min_devices:
        raise SystemExit(f"chip_smoke: needs {min_devices} chips, found "
                         f"{len(devs)}")
    resolved = ops._resolve("auto")
    if resolved != "pallas":
        raise SystemExit(f"chip_smoke: kernel_backend='auto' resolves to "
                         f"{resolved!r}, not 'pallas' (is "
                         f"{ops.ENV_BACKEND} set?)")
    return devs


def _finite(res, what):
    import numpy as np
    vals = list(res.losses) + list(res.rewards)
    if not res.losses or not np.isfinite(vals).all():
        raise AssertionError(f"{what}: non-finite or missing losses/rewards "
                             f"{res.losses} {res.rewards}")


def _train(env_name, topology, actor_backend, net_kwargs, *, mesh=None,
           calib_batch=0, seed=0):
    from repro.rl import loops
    return loops.train(
        "dqn", env_name, topology=topology, num_actors=NUM_ACTORS,
        actor_backend=actor_backend, calib_batch=calib_batch,
        iterations=ITERATIONS, steps_per_call=STEPS_PER_CALL,
        record_every=ITERATIONS // 2, eval_episodes=EVAL_EPISODES,
        sync_every=2 if topology == "actor-learner" else 4,
        net_kwargs=net_kwargs, algo_overrides=dict(DQN), mesh=mesh,
        seed=seed)


def _conv_kwargs():
    from repro.configs.quarl_atari import ATARI_DQN
    return dict(conv_filters=ATARI_DQN.conv_filters,
                fc_width=ATARI_DQN.fc_width)


def _mlp_kwargs():
    from repro.configs.quarl_atari import DEPLOY_POLICY_II
    return dict(hidden=DEPLOY_POLICY_II.widths)


def phase_conv():
    """(b): the conv actor, per-layer int8 GEMMs through im2col."""
    res = _train("catch", "actor-learner", "int8", _conv_kwargs())
    _finite(res, "conv actor-learner int8")
    return res


def phase_mlp():
    """(c): the MLP actor on the fused kernel, int8/int4 x two topologies."""
    out = {}
    for actor_backend in ("int8", "int4"):
        for topology in ("actor-learner", "async"):
            res = _train("cartpole", topology, actor_backend, _mlp_kwargs(),
                         calib_batch=CALIB_BATCH)
            _finite(res, f"mlp {topology} {actor_backend}")
            out[(actor_backend, topology)] = res
    return out


def env_obs(env_name, n, seed):
    """``n`` observations a few random steps past reset."""
    import jax
    from repro.rl.env import batched_env
    from repro.rl.envs import make as make_env
    env = make_env(env_name)
    benv = batched_env(env, n)
    key = jax.random.PRNGKey(seed)
    state, obs = benv.reset(key)
    for t in range(8):
        k = jax.random.fold_in(key, t)
        a = jax.random.randint(k, (n,), 0, env.spec.n_actions)
        state, obs, _, _ = benv.step(state, a, jax.random.fold_in(k, 1))
    return obs


def assert_kernel(compiled_text, what):
    """The compiled program calls a Pallas kernel."""
    if "tpu_custom_call" not in compiled_text:
        raise AssertionError(f"{what}: the compiled actor step runs no "
                             f"Pallas kernel")


def assert_parity(cache, obs, what):
    """Pallas forward == ref forward on ``obs``; the step holds a kernel."""
    import jax
    import numpy as np
    from repro.rl import actorq

    def fwd(backend):
        return jax.jit(lambda c, o: actorq.quantized_apply(
            c, o, backend=backend))

    assert_kernel(fwd("auto").lower(cache, obs).compile().as_text(), what)
    got = np.asarray(fwd("auto")(cache, obs))
    want = np.asarray(fwd("ref")(cache, obs))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL,
                               err_msg=what)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1),
                                  err_msg=f"{what}: greedy actions")


def phase_parity(conv_res, mlp_res):
    """(d): parity at the timed size, on the trained and the wide caches."""
    import jax
    from repro.configs.quarl_atari import DEPLOY_POLICY_III
    from repro.rl import actorq
    from repro.rl.networks import make_network

    catch_obs = env_obs("catch", 256, 1)
    assert_parity(actorq.make_actor_cache(conv_res.state.params, "int8"),
                  catch_obs, "conv int8")
    cart_obs = env_obs("cartpole", 256, 2)
    for (actor_backend, topology), res in mlp_res.items():
        cache = actorq.make_actor_cache(
            res.state.params, actor_backend,
            calib_obs=cart_obs[:CALIB_BATCH])
        assert_parity(cache, cart_obs, f"mlp {topology} {actor_backend}")
    nav_obs = env_obs("airnav", 256, 3)
    net = make_network(nav_obs.shape[1:], 25, hidden=DEPLOY_POLICY_III.widths)
    params = net.init(jax.random.PRNGKey(4))
    for actor_backend in ("int8", "int4"):
        cache = actorq.make_actor_cache(params, actor_backend,
                                        calib_obs=nav_obs[:CALIB_BATCH])
        for batch in (1, 256):
            assert_parity(cache, nav_obs[:batch],
                          f"policy_iii {actor_backend} batch {batch}")


def phase_serving(mlp_res):
    """(e): PolicyServer answers SESSIONS sessions across one hot-swap."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.rl import actorq
    from repro.rl.env import batched_env
    from repro.serving import PolicyServer
    from repro.serving.server import greedy_calib_obs

    first = mlp_res[("int8", "actor-learner")].state.params
    second = mlp_res[("int8", "async")].state.params
    env = mlp_res[("int8", "actor-learner")].env
    ref_act = jax.jit(actorq.make_act_fn(env.spec, backend="ref"))
    server = PolicyServer(env.spec, actor_backend="int8",
                          calib_batch=CALIB_BATCH)
    calib = greedy_calib_obs(env, actorq.pack_actor_params(first),
                             CALIB_BATCH)
    caches = {}
    entry = server.push_params(first, calib_obs=calib)
    caches[entry.version] = entry.cache
    server.warmup()
    sids = [server.open_session() for _ in range(SESSIONS)]
    benv = batched_env(env, SESSIONS)
    key = jax.random.PRNGKey(5)
    state, obs = benv.reset(key)
    with server:
        for step in range(SERVE_STEPS):
            if step == SERVE_STEPS // 2:
                entry = server.push_params(second)     # the hot-swap
                caches[entry.version] = entry.cache
            obs_np = np.asarray(obs)
            reqs = [server.submit(sid, obs_np[i])
                    for i, sid in enumerate(sids)]
            results = [r.result(timeout=120) for r in reqs]
            actions = np.array([r.action for r in results])
            versions = np.array([r.version for r in results])
            for v in np.unique(versions):
                want = np.asarray(ref_act(caches[int(v)], obs))
                rows = versions == v
                np.testing.assert_array_equal(
                    actions[rows], want[rows],
                    err_msg=f"served actions, step {step}, cache v{v}")
            state, obs, _, _ = benv.step(state, jnp.asarray(actions),
                                         jax.random.fold_in(key, step))
    if len(caches) != 2 or server.stats()["served"] < SESSIONS * SERVE_STEPS:
        raise AssertionError(f"serving: {server.stats()}")


def _check_mesh_run(res, n_dev):
    """Actor-axis arrays on ``n_dev`` devices; replicated params equal."""
    import jax
    import numpy as np
    actor_axis = jax.tree_util.tree_leaves(
        (res.state.extras.replay, res.env_state))
    for leaf in actor_axis:
        if len(leaf.sharding.device_set) != n_dev:
            raise AssertionError(f"actor-axis array on "
                                 f"{leaf.sharding.device_set}")
    for leaf in jax.tree_util.tree_leaves(res.state.params):
        shards = leaf.addressable_shards
        if len({s.device for s in shards}) != n_dev:
            raise AssertionError(f"params on {leaf.sharding}")
        first = np.asarray(shards[0].data)
        for s in shards[1:]:
            np.testing.assert_array_equal(np.asarray(s.data), first,
                                          err_msg="learner replicas differ")


def phase_four_chips():
    """Sharded actors over 4 chips against the same run on one chip."""
    import jax
    mesh = jax.make_mesh((NUM_ACTORS,), ("actor",))
    env_steps = ITERATIONS * NUM_ACTORS * DQN["n_envs"] * DQN["rollout_steps"]
    for topology in ("actor-learner", "async"):
        for label, m in (("1 chip", None), ("4 chips", mesh)):
            _train("cartpole", topology, "int8", _mlp_kwargs(), mesh=m,
                   calib_batch=CALIB_BATCH)               # compile
            res = _train("cartpole", topology, "int8", _mlp_kwargs(), mesh=m,
                         calib_batch=CALIB_BATCH)
            _finite(res, f"{topology} {label}")
            if m is not None:
                _check_mesh_run(res, NUM_ACTORS)
                replay = jax.tree_util.tree_leaves(res.state.extras.replay)
                devs = sorted(d.id for d in replay[0].sharding.device_set)
                print(f"four-chips {topology}: replay and env state on "
                      f"devices {devs}; learner params bitwise-equal on "
                      f"all {NUM_ACTORS} replicas", flush=True)
            print(f"four-chips {topology} {label}: "
                  f"{env_steps / res.wall_time_s:.1f} env-steps/s "
                  f"({env_steps} env steps in {res.wall_time_s:.3f} s, "
                  f"evals included); losses {res.losses}", flush=True)


def main(argv=None) -> int:
    """Run the phases; print the result line last; return 0."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded actor path on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()

    devs = check_device(NUM_ACTORS if args.four_chips else 1)
    print(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
          f"{cache_dir}", flush=True)
    if args.four_chips:
        phase_four_chips()
        print("phase four-chips: passed", flush=True)
    else:
        conv = run_phase(counter, "b conv actor-learner int8", phase_conv)
        mlp = run_phase(counter,
                        "c mlp fused int8/int4 x actor-learner/async",
                        phase_mlp)
        run_phase(counter, "d parity pallas == ref",
                  lambda: phase_parity(conv, mlp))
        run_phase(counter, "e serving 512 sessions + hot-swap",
                  lambda: phase_serving(mlp))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
