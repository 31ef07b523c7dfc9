"""Training loops + the QuaRL pipelines (paper Algorithms 1 and 2).

``train(...)`` runs any of the four algorithms on any env;
``quarl_ptq(...)``  = Algorithm 1: M = Train(T, L, A); return Eval(Q(M)).
``quarl_qat(...)``  = Algorithm 2: insert fake-quant ops, monitor ranges for
``quant_delay`` updates, then train with quantization; Eval with Q^train.

Both return a ``QuarlResult`` with fp32 and quantized rewards plus the
paper's relative error E_%.

Hot-path knobs (ActorQ):

* ``steps_per_call`` — the scan-fused driver. ``make_scan_iteration`` wraps
  any algorithm's jitted iteration in a ``jax.lax.scan`` over a chunk of
  ``steps_per_call`` updates inside ONE jit with donated
  ``(state, env_state, obs)`` buffers, so the Python driver pays one
  dispatch per chunk instead of one per update.  Numerically equivalent to
  the per-step driver (same seed -> same params, bitwise on CPU): the PRNG
  split chain moves into the scan carry unchanged.
* ``actor_backend`` — ``"fp32"`` (default), ``"int8"`` or ``"int4"``.
  With ``"int8"`` the *actor* runs true integer inference (``rl.actorq``):
  params are packed into an int8 cache once per learner update and every
  dense/conv layer goes through the W8A8 kernel
  (``kernels.ops.int8_matmul``; backend matrix
  pallas/interpret/ref/xla/auto).  ``"int4"`` stores the cache as byte-packed
  W4A8 codes (half the bytes, unpacked in-kernel).  Rollout data
  collection uses the quantized actor for all four algorithms; evaluation
  uses it for every algorithm.  The learner's gradient path stays fp32 —
  exactly the paper's ActorQ split.
* ``calib_batch`` — static-requant knob (quantized backends, MLP
  policies): calibrate per-layer activation scales from this many live
  observations at every cache refresh and run the actor forward as ONE
  fused kernel pass (``kernels.fused_qmlp``) with int8-resident
  inter-layer activations — no per-layer dynamic range pass, one dispatch
  instead of ``n_layers``.  0 keeps dynamic per-layer quantization.
* ``topology`` — ``"fused"`` (default), ``"actor-learner"``, or
  ``"async"``.  ``"actor-learner"`` runs the paper's distributed ActorQ
  paradigm (``rl.actor_learner``) for the replay algorithms (DQN/DDPG):
  ``num_actors`` actor replicas collect rollouts (int8 under
  ``actor_backend="int8"``) into a sharded replay buffer, the fp32
  learner samples per-shard batches, and refreshed params reach the
  actors every ``sync_every`` iterations (the staleness knob) — one
  iteration is bulk-synchronous.  ``"async"`` is the overlapped regime
  the paper's speedups come from: actors and learner compile to two
  independent jit programs over a double-buffered replay
  (``rl.buffer.DoubleBuffer``), the host dispatches both with no
  ``block_until_ready`` barrier, swaps the write/read slots at sync
  points, and ``sync_every`` counts *learner updates* between param
  pushes.  Per-actor int8-vs-fp32 divergence is recorded in
  ``TrainResult.divergences`` at true pushes only; ``"async"``
  additionally records per-sync actor lag (``TrainResult.actor_lags``).
* ``replay`` — ``"uniform"`` (default) or ``"prioritized"`` (DQN/DDPG).
  Prioritized experience replay on a fully-JAX sum-tree (``rl.buffer``):
  the learner samples proportionally to
  ``(|td| + eps) ** priority_exponent``, corrects the bias with
  importance-sampling weights annealed from ``is_beta`` to 1, and pushes
  refreshed |TD| priorities after every update.  Under the actor–learner
  topology every shard owns its own tree and priority pushes stay inside
  the shard_map.  ``priority_exponent=0.0`` is bitwise-uniform (static
  dispatch onto the uniform path — the ``num_actors=1, sync_every=1``
  contract style).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import metrics as metrics_lib
from repro.core.qconfig import QuantConfig, QuantMode
from repro.rl import a2c, actor_learner, actorq, common, ddpg, dqn, ppo
from repro.rl import buffer as rb
from repro.rl.env import Env, evaluate
from repro.rl.envs import make as make_env
from repro.rl.networks import make_network

ALGOS = ("dqn", "a2c", "ppo", "ddpg")


def _bootstrap_observers(algo, env, net, state, quant):
    """Pre-create every QAT observer slot (scan carries need fixed pytrees)."""
    from repro.core import fake_quant
    import jax.numpy as jnp
    obs0 = jnp.zeros((2,) + tuple(env.spec.obs_shape))

    if algo == "ddpg":
        def trace(rec):
            a = jnp.tanh(net.actor.apply(common.PrefixCtx(rec, "actor/"),
                                         state.params, obs0))
            x = jnp.concatenate([obs0.reshape(2, -1), a], axis=-1)
            net.critic.apply(common.PrefixCtx(rec, "critic/"),
                             state.extras.critic_params, x)
    else:
        def trace(rec):
            net.apply(rec, state.params, obs0)
    return fake_quant.discover_observers(quant, trace)


@dataclasses.dataclass
class TrainResult:
    """Everything ``train`` hands back: the final ``state`` (params +
    optimizer), the deterministic ``act_fn(params, obs)``, the ``env``,
    per-record ``rewards``/``action_variances``, wall time, and the
    resolved algo config / network — enough to eval, deploy
    (``serving.PolicyServer.push_params(result.state.params)``), or
    resume."""

    state: common.TrainState
    act_fn: Callable
    env: Env
    rewards: List[float]
    action_variances: List[float]
    wall_time_s: float
    algo_cfg: Any
    net: Any
    # actor-learner topologies only: [per-actor mean-abs divergence between
    # the actors' behaviour head and the fp32 learner], sampled at true
    # param pushes only — per record point for topology="actor-learner"
    # (the last push's value carries between records; nothing is recorded
    # before the first push), per sync for topology="async"
    divergences: List[List[float]] = dataclasses.field(default_factory=list)
    # topology="async" only: per sync, how many learner updates the retired
    # actor snapshot served for (the realized staleness, >= sync_every)
    actor_lags: List[int] = dataclasses.field(default_factory=list)
    # the learner's mean loss over the last chunk, per record point
    losses: List[float] = dataclasses.field(default_factory=list)
    # the final batched env state (sharded over the actor axis under a mesh)
    env_state: Any = None


def make_scan_iteration(iteration: Callable, steps_per_call: int):
    """Fuse ``steps_per_call`` algorithm iterations into one jitted scan.

    ``iteration(state, env_state, obs, key) -> (state, env_state, obs,
    metrics)`` is any algo's update (the already-jitted function from
    ``make_iteration`` works; jit-of-jit inlines).  The returned ``chunk``
    has signature ``chunk(state, env_state, obs, key) -> (state, env_state,
    obs, key, metrics)`` where ``key`` is the advanced run key and
    ``metrics`` is the per-iteration metrics dict stacked to shape
    ``(steps_per_call,)`` — accumulated on device, transferred once per
    chunk.

    The per-iteration PRNG chain (``key, k_it = split(key)``) runs inside
    the scan carry, byte-for-byte the chain the per-step driver produces on
    the host — so the two drivers are bitwise equivalent on CPU.
    ``(state, env_state, obs)`` buffers are donated: the carry updates in
    place instead of round-tripping fresh allocations per update.
    """
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def chunk(state, env_state, obs, key):
        def body(carry, _):
            state, env_state, obs, key = carry
            key, k_it = jax.random.split(key)
            state, env_state, obs, metrics = iteration(state, env_state,
                                                       obs, k_it)
            return (state, env_state, obs, key), metrics

        (state, env_state, obs, key), metrics = jax.lax.scan(
            body, (state, env_state, obs, key), None, length=steps_per_call)
        return state, env_state, obs, key, metrics

    return chunk


def _build(algo: str, env: Env, quant: QuantConfig, net_kwargs: Dict,
           overrides: Dict):
    if algo == "ddpg":
        assert env.spec.continuous, f"DDPG needs continuous env"
        nets = ddpg.make_nets(env, **net_kwargs)
        cfg = dataclasses.replace(ddpg.DDPGConfig(quant=quant), **overrides)
        return nets, cfg
    out_dim = env.spec.n_actions
    if algo in ("a2c", "ppo"):
        out_dim += 1  # value head
    net = make_network(env.spec.obs_shape, out_dim, **net_kwargs)
    if algo == "dqn":
        cfg = dataclasses.replace(dqn.DQNConfig(quant=quant), **overrides)
    elif algo == "a2c":
        cfg = dataclasses.replace(a2c.A2CConfig(quant=quant), **overrides)
    else:
        cfg = dataclasses.replace(ppo.PPOConfig(quant=quant), **overrides)
    return net, cfg


def _loop_checkpointer(checkpoint_dir, checkpoint_every, resume, keep):
    """``AsyncCheckpointer`` for the train drivers, or None when disabled.

    Catches knob typos loudly: ``checkpoint_every``/``resume`` without a
    directory would otherwise silently train with no fault tolerance.
    """
    if not checkpoint_dir:
        if resume:
            raise ValueError("resume=True needs checkpoint_dir")
        if checkpoint_every:
            raise ValueError("checkpoint_every > 0 needs checkpoint_dir")
        return None
    from repro import checkpoint as ckpt_lib
    return ckpt_lib.AsyncCheckpointer(checkpoint_dir, keep=keep)


def train(algo: str, env_name: str, *, iterations: int = 200,
          quant: QuantConfig = QuantConfig.none(), seed: int = 0,
          net_kwargs: Optional[Dict] = None,
          algo_overrides: Optional[Dict] = None,
          record_every: int = 10, eval_episodes: int = 8,
          steps_per_call: int = 1,
          actor_backend: str = "fp32", calib_batch: int = 0,
          topology: str = "fused", num_actors: int = 1,
          sync_every: int = 1, mesh=None, async_barrier: bool = False,
          replay: str = "uniform", priority_exponent: float = 0.6,
          is_beta: float = 0.4,
          checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
          resume: bool = False, checkpoint_keep: int = 3,
          resilience: Any = None) -> TrainResult:
    """Train ``algo`` on ``env_name``.

    ``steps_per_call > 1`` enables the scan-fused driver (see module
    docstring): the Python loop dispatches ``iterations / steps_per_call``
    fused chunks instead of one jit call per update, with chunks clipped to
    ``record_every`` boundaries so recorded rewards/metrics are identical.

    ``actor_backend="int8"`` runs rollout data collection (all four
    algorithms) and the periodic evaluations through the true-int8 actor
    (``rl.actorq``); the learner stays fp32.  ``"int4"`` packs the actor
    cache to byte-packed W4A8 codes — half the int8 cache and sync/snapshot
    bytes, same 8-bit activation protocol.

    ``calib_batch > 0`` (quantized backends, MLP policies): every cache
    refresh also calibrates *static* activation scales from that many live
    rollout observations, replacing the per-layer dynamic range pass and
    running the actor forward through the single-pass fused kernel
    (``kernels.ops.fused_qmlp``).  0 (default) keeps the PR-1 dynamic
    per-layer path bitwise unchanged.

    ``topology="actor-learner"`` (DQN/DDPG) runs the paper's distributed
    ActorQ paradigm with ``num_actors`` replicas and a ``sync_every``
    staleness cadence — see ``rl.actor_learner``; ``mesh`` optionally
    shards the actor axis over devices.

    ``topology="async"`` (DQN/DDPG) overlaps the two: actor rollout chunks
    (``steps_per_call`` rollouts per dispatch) and learner update chunks
    run as independent jit programs over a double-buffered replay with no
    host barrier between them; ``sync_every`` counts *learner updates*
    between param pushes (each round runs
    ``steps_per_call * updates_per_iter`` updates, so pushes land on the
    first round boundary reaching the cadence).  ``async_barrier=True`` is
    the equivalence-contract mode: a single replay slot threaded
    actor -> learner serializes each round by dataflow, and with
    ``steps_per_call=1`` + ``sync_every=updates_per_iter`` the learner
    trajectory is bitwise identical to ``topology="actor-learner"`` with
    ``sync_every=1`` (the anchor test).

    ``replay="prioritized"`` (DQN/DDPG) samples learner batches
    proportionally to per-transition ``(|td| + eps) ** priority_exponent``
    from a fully-JAX sum-tree (per actor shard under the actor–learner
    topology) with importance-sampling correction annealed from
    ``is_beta`` to 1 — see ``rl.buffer``.  ``priority_exponent=0.0``
    degrades to bitwise-uniform sampling.

    ``checkpoint_dir`` + ``checkpoint_every`` enable fault tolerance
    (``repro.checkpoint``, all topologies): every ``checkpoint_every``
    iterations an ``AsyncCheckpointer`` snapshots learner + optimizer
    state, replay buffer (uniform and PER sum-trees), packed actor
    caches, env state, RNG keys and the host-side metric lists to
    ``checkpoint_dir`` on a background writer thread — the jit'd step
    never blocks on disk.  ``resume=True`` restarts from the newest
    committed step, and the contract is bitwise: resume-at-k then
    train-to-n equals the uninterrupted run to n exactly (checkpoint
    cadence never alters chunk boundaries or the PRNG chain; anchor
    tests in ``tests/test_resume.py``).  ``checkpoint_keep`` bounds
    retention; see ``docs/checkpointing.md``.

    ``resilience`` (optional) is a duck-typed hook object — in practice
    ``repro.resilience.ResilienceContext`` — giving the self-healing
    runtime its host-side injection/guard points: ``round_start`` /
    ``after_round`` around every dispatched chunk, ``on_eval_cache`` on
    the quantized eval mint, ``push`` around async param pushes, and
    ``checkpoint_committed`` after saves.  All hooks run on the host
    between jitted chunks, so an un-faulted guarded run follows the
    exact chunk/PRNG schedule of a bare one (the bitwise-recovery
    contract; see docs/resilience.md).  None (default) = zero overhead.
    """
    actorq.validate_actor_backend(actor_backend)
    actor_learner.validate_topology(topology)
    rb.validate_replay(replay)
    env = make_env(env_name)
    overrides = dict(algo_overrides or {})
    overrides.setdefault("actor_backend", actor_backend)
    overrides.setdefault("calib_batch", calib_batch)
    if algo in actor_learner.ALGOS:      # the replay algorithms (DQN/DDPG)
        overrides.setdefault("replay", replay)
        overrides.setdefault("priority_exponent", priority_exponent)
        overrides.setdefault("is_beta", is_beta)
    elif rb.validate_replay(overrides.get("replay", replay)) != "uniform":
        raise ValueError(
            f"replay='prioritized' needs a replay algorithm "
            f"{actor_learner.ALGOS}; {algo!r} is on-policy")
    net, cfg = _build(algo, env, quant, net_kwargs or {}, overrides)
    mod = {"dqn": dqn, "a2c": a2c, "ppo": ppo, "ddpg": ddpg}[algo]
    key = jax.random.PRNGKey(seed)
    k_init, k_env, k_run = jax.random.split(key, 3)
    if topology == "async":
        if algo not in actor_learner.ALGOS:
            raise ValueError(
                f"topology='async' needs a replay algorithm "
                f"{actor_learner.ALGOS}, got {algo!r}")
        if quant.is_qat:
            raise ValueError("async topology does not support QAT "
                             "(the learner trains fp32; use PTQ eval)")
        return _train_async(
            algo, env, net, cfg, iterations=iterations,
            record_every=record_every, eval_episodes=eval_episodes,
            steps_per_call=steps_per_call, num_actors=num_actors,
            sync_every=sync_every, mesh=mesh, barrier=async_barrier,
            actor_backend=actor_backend, k_init=k_init, k_env=k_env,
            k_run=k_run, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            checkpoint_keep=checkpoint_keep, resilience=resilience)
    if async_barrier:
        raise ValueError("async_barrier is an async-topology knob — pass "
                         "topology='async'")
    if topology == "actor-learner":
        if algo not in actor_learner.ALGOS:
            raise ValueError(
                f"topology='actor-learner' needs a replay algorithm "
                f"{actor_learner.ALGOS}, got {algo!r}")
        if quant.is_qat:
            raise ValueError("actor-learner topology does not support QAT "
                             "(the learner trains fp32; use PTQ eval)")
        al_cfg = actor_learner.ActorLearnerConfig(num_actors=num_actors,
                                                  sync_every=sync_every)
        state = actor_learner.init(k_init, env, net, algo, cfg, al_cfg)
        iteration, act_fn, benv = actor_learner.make_actor_learner(
            algo, env, net, cfg, al_cfg, mesh=mesh)
    elif num_actors != 1 or sync_every != 1 or mesh is not None:
        raise ValueError(
            "num_actors/sync_every/mesh are actor-learner knobs — pass "
            "topology='actor-learner' (the fused driver would silently "
            "ignore them)")
    else:
        state = mod.init(k_init, env, net, cfg)
        if quant.is_qat:
            state = state._replace(
                observers=_bootstrap_observers(algo, env, net, state,
                                               quant))
        iteration, act_fn, benv = mod.make_iteration(env, net, cfg)
    env_state, obs = benv.reset(k_env)

    kernel_backend = getattr(cfg, "kernel_backend", "auto")
    int8_act = actorq.make_act_fn(env.spec, backend=kernel_backend) \
        if actorq.is_quantized(actor_backend) else None
    # stable act-fn identity across the run -> evaluate() compiles once;
    # observers/step ride along in the params slot as traced inputs
    det_act = _det_act(act_fn)
    evaluate_at = _make_record_eval(env, cfg, mesh, actor_backend,
                                    kernel_backend, int8_act, det_act,
                                    eval_episodes, resilience)
    chunks: Dict[int, Callable] = {}   # compiled fused drivers by length

    rewards, variances, divergences, losses = [], [], [], []
    ckptr = _loop_checkpointer(checkpoint_dir, checkpoint_every, resume,
                               checkpoint_keep)
    i = 0
    if ckptr is not None and resume:
        start = ckptr.latest_step()
        if start is not None:
            # template = the freshly initialized run state: same
            # seed/config -> same treedef, and restore() validates every
            # leaf's shape/dtype against it before touching anything
            tree, extra = ckptr.restore(
                start, {"state": state, "env_state": env_state,
                        "obs": obs, "key": k_run})
            state, env_state, obs, k_run = (
                tree["state"], tree["env_state"], tree["obs"], tree["key"])
            i = int(extra["iteration"])
            rewards = [float(r) for r in extra["rewards"]]
            variances = [float(v) for v in extra["action_variances"]]
            divergences = [list(d) for d in extra["divergences"]]
            losses = [float(x) for x in extra.get("losses", [])]
    if mesh is not None:
        # commit the carry to the mesh once, under the programs' own
        # specs: a donated input aliases its output only when both share
        # a sharding, and arrays made off the mesh sit on one device
        state = actor_learner.place(state, mesh,
                                    actor_learner.mesh_specs(state))
        env_state, obs = actor_learner.place((env_state, obs), mesh,
                                             P("actor"))
    last_saved = i
    t0 = time.time()
    try:
        while i < iterations:
            if resilience is not None:
                resilience.round_start(i)
                resilience.dropped_sync_na(i, topology)
            # clip chunks to record boundaries so the recorded
            # metrics/rewards (and their PRNG draws) match the per-step
            # driver exactly
            next_stop = min((i // record_every + 1) * record_every,
                            iterations)
            n = min(max(steps_per_call, 1), next_stop - i)
            if n not in chunks:
                chunks[n] = make_scan_iteration(iteration, n)
            with jax.profiler.TraceAnnotation("train.chunk"):
                state, env_state, obs, k_run, metrics = chunks[n](
                    state, env_state, obs, k_run)
            i += n
            if resilience is not None:
                state = _guard_round(resilience, state, i, cfg,
                                     actor_backend, kernel_backend)
            if i % record_every == 0 or i == iterations:
                last = jax.tree_util.tree_map(lambda m: m[-1], metrics)
                # actor-learner states carry the fp32 learner inside
                lview = state.learner \
                    if isinstance(state, actor_learner.ActorLearnerState) \
                    else state
                with jax.profiler.TraceAnnotation("train.eval"):
                    r, k_run = evaluate_at(
                        (lview.params, lview.observers, lview.step), obs,
                        k_run, i)
                rewards.append(r)
                losses.append(float(last["loss"]))
                variances.append(float(last.get(
                    "action_dist_variance", last.get("mean_q_var", 0.0))))
                # staleness contract: the first true push happens at
                # iteration sync_every, so record points before it would
                # only see the init-time zeros (t=0 is not a sync — the
                # actors hold a fresh copy by construction) and are
                # skipped
                if "divergence" in last and i >= sync_every:
                    divergences.append(
                        np.asarray(last["divergence"]).tolist())
            if ckptr is not None and checkpoint_every > 0 and (
                    i - last_saved >= checkpoint_every or
                    (i == iterations and i > last_saved)):
                # end of the loop body: the saved key and metric lists
                # already include this boundary's eval draws, so a resumed
                # run continues the PRNG chain bitwise.  Cadence never
                # clips chunks — the chunk-boundary sequence is a function
                # of i alone, identical with or without checkpointing.
                with jax.profiler.TraceAnnotation("train.checkpoint"):
                    ckptr.save_async(
                        i, {"state": state, "env_state": env_state,
                            "obs": obs, "key": k_run},
                        extra={"iteration": i, "rewards": rewards,
                               "action_variances": variances,
                               "divergences": divergences,
                               "losses": losses})
                last_saved = i
                if resilience is not None:
                    resilience.checkpoint_committed(ckptr, i)
        wall = time.time() - t0
        if ckptr is not None:
            ckptr.wait()
    finally:
        # an escaping fault/guard error must not leak the writer thread:
        # the supervisor's next attempt opens its own checkpointer on the
        # same directory (single-writer discipline holds per attempt)
        if ckptr is not None:
            ckptr.close()
    if isinstance(state, actor_learner.ActorLearnerState):
        state = state.learner
    return TrainResult(state=state, act_fn=act_fn, env=env, rewards=rewards,
                       action_variances=variances, wall_time_s=wall,
                       algo_cfg=cfg, net=net, divergences=divergences,
                       losses=losses, env_state=env_state)


def _eval_inputs(mesh, *trees):
    """The inputs of an evaluation (params, obs, key), on one device.

    Evaluation is one small unsharded program; on a mesh its inputs move
    to the mesh's first device, because the compiler cannot partition the
    quantized actor's Pallas kernels over the mesh.  They go through the
    host: a ``device_put`` from an explicitly sharded mesh keeps the
    mesh axes in the arrays' types.
    """
    if mesh is None:
        return trees
    return jax.device_put(jax.tree_util.tree_map(np.asarray, trees),
                          mesh.devices.flat[0])


def _make_record_eval(env, cfg, mesh, actor_backend, kernel_backend,
                      int8_act, det_act, eval_episodes, resilience):
    """``evaluate_at(params_view, obs, k_run, i) -> (reward, k_run)``: the
    record-point evaluation both drivers run, on the learner's
    ``(params, observers, step)``.

    Quantized actors are evaluated in the configuration that collects data
    and gets deployed: with ``calib_batch`` the eval cache is calibrated
    from the live observations and runs the fused kernel."""
    cb = getattr(cfg, "calib_batch", 0)

    def evaluate_at(params_view, obs, k_run, i):
        k_run, k_eval = jax.random.split(k_run)
        pview, obs_e, k_eval = _eval_inputs(mesh, params_view, obs, k_eval)
        if int8_act is None:
            r = evaluate(env, det_act, pview, k_eval, eval_episodes,
                         max_steps=env.spec.max_steps)
            return float(r), k_run
        obs_g = obs_e.reshape((-1,) + tuple(env.spec.obs_shape))

        def mint_eval(p=pview[0], og=obs_g):
            return actorq.make_actor_cache(
                p, actor_backend,
                calib_obs=actorq.calib_slice(og, cb) if cb else None,
                backend=kernel_backend)

        qparams = mint_eval()
        if resilience is not None:
            qparams = resilience.on_eval_cache(qparams, i, mint_eval)
        r = evaluate(env, int8_act, qparams, k_eval, eval_episodes,
                     max_steps=env.spec.max_steps)
        return float(r), k_run
    return evaluate_at


def _guard_round(resilience, state, step, cfg, actor_backend,
                 kernel_backend):
    """Topology-aware ``after_round`` adapter for the sync drivers.

    Maps the resilience hooks onto the state shape: the fused driver's
    ``TrainState`` exposes its params directly; ``ActorLearnerState``
    additionally carries the packed actor cache, which is both the
    bitflip_push target and — when minting is deterministic
    (``calib_batch == 0``) — verifiable against a fresh repack of the
    stale actor params (the in-jit sync mint and the eager re-mint are
    the same ops on the same buffers; CPU bitwise parity is the repo's
    standing fused-vs-per-step anchor).  All host-side: corruption and
    verification never touch the jitted chunk schedule.
    """
    is_al = isinstance(state, actor_learner.ActorLearnerState)
    if is_al:
        state = resilience.after_round(
            state, step,
            learner_view=lambda s: s.learner.params,
            set_learner=lambda s, p: s._replace(
                learner=s.learner._replace(params=p)),
            repack=lambda s, fn: s if s.actor_cache == ()
            else actor_learner.with_cache(s, fn(s.actor_cache)))
        cb = getattr(cfg, "calib_batch", 0)
        if (actorq.is_quantized(actor_backend) and cb == 0
                and state.actor_cache != ()
                and step % max(resilience.guard.check_every, 1) == 0):
            resilience.verify_state_cache(
                state.actor_cache,
                functools.partial(actor_learner.remint_cache, state,
                                  actor_backend,
                                  kernel_backend=kernel_backend),
                step)
        return state
    return resilience.after_round(
        state, step,
        learner_view=lambda s: s.params,
        set_learner=lambda s, p: s._replace(params=p))


def _train_async(algo, env, net, cfg, *, iterations, record_every,
                 eval_episodes, steps_per_call, num_actors, sync_every,
                 mesh, barrier, actor_backend, k_init, k_env, k_run,
                 checkpoint_dir=None, checkpoint_every=0, resume=False,
                 checkpoint_keep=3, resilience=None) -> TrainResult:
    """The ``topology="async"`` host driver: overlapped dispatch.

    Each round dispatches one actor chunk (``steps_per_call`` rollouts
    into the write slot) and one learner chunk
    (``steps_per_call * updates_per_iter`` updates against the read slot)
    back-to-back — JAX's async dispatch queues both with **no**
    ``block_until_ready`` between them; within a sync period the two
    program chains share no buffers, so the runtime is free to overlap
    them.  At sync points the host swaps the slots (a reference exchange,
    no device op) and pushes a fresh param snapshot; the divergence
    program is dispatched there too and only materialized at the end.
    The periodic evaluation at ``record_every`` boundaries is the one
    place the driver synchronizes (it reads rewards back to the host) —
    between records the loop never blocks.

    ``barrier=True`` threads a single replay slot actor -> learner, which
    serializes each round by dataflow — the equivalence-contract mode
    (see ``train``).
    """
    al_cfg = actor_learner.ActorLearnerConfig(num_actors=num_actors,
                                              sync_every=sync_every)
    progs = actor_learner.make_async_actor_learner(algo, env, net, cfg,
                                                   al_cfg, mesh=mesh)
    learner, wbuf = actor_learner.init_async(k_init, env, net, algo, cfg,
                                             al_cfg, double=not barrier)
    env_state, obs = progs.benv_global.reset(k_env)
    # snapshot after reset: with calib_batch the t=0 mint calibrates its
    # static activation scales from the fresh initial observations
    snap = progs.make_snapshot(learner, obs)

    kernel_backend = getattr(cfg, "kernel_backend", "auto")
    int8_act = actorq.make_act_fn(env.spec, backend=kernel_backend) \
        if actorq.is_quantized(actor_backend) else None
    det_act = _det_act(progs.act_fn)
    evaluate_at = _make_record_eval(env, cfg, mesh, actor_backend,
                                    kernel_backend, int8_act, det_act,
                                    eval_episodes, resilience)

    rewards, variances, actor_lags, losses = [], [], [], []
    div_futs: List[Any] = []      # per-sync futures, materialized at the end
    updates_since_push = 0
    total_updates = 0             # learner updates dispatched (host-side)
    snap_minted_at = 0
    ckptr = _loop_checkpointer(checkpoint_dir, checkpoint_every, resume,
                               checkpoint_keep)
    i = 0
    if ckptr is not None and resume:
        start = ckptr.latest_step()
        if start is not None:
            # barrier mode threads ONE slot through learner.extras.replay
            # (wbuf is reassigned from it each round), so saving wbuf too
            # would duplicate the buffer — it checkpoints as None there
            tree, extra = ckptr.restore(
                start, {"learner": learner,
                        "wbuf": None if barrier else wbuf,
                        "env_state": env_state, "obs": obs, "snap": snap,
                        "key": k_run})
            learner, wbuf, env_state, obs, snap, k_run = (
                tree["learner"], tree["wbuf"], tree["env_state"],
                tree["obs"], tree["snap"], tree["key"])
            i = int(extra["iteration"])
            rewards = [float(r) for r in extra["rewards"]]
            variances = [float(v) for v in extra["action_variances"]]
            actor_lags = [int(x) for x in extra["actor_lags"]]
            losses = [float(x) for x in extra.get("losses", [])]
            div_futs = [np.asarray(d, dtype=np.float32)
                        for d in extra["divergences"]]
            updates_since_push = int(extra["updates_since_push"])
            total_updates = int(extra["total_updates"])
            snap_minted_at = int(extra["snap_minted_at"])
    if mesh is not None:
        # one placement, as in the synchronous driver (the snapshot too:
        # a restored one comes off the mesh; later ones are minted on it)
        learner = actor_learner.place(learner, mesh,
                                      actor_learner.mesh_specs(learner))
        wbuf, env_state, obs = actor_learner.place(
            (wbuf, env_state, obs), mesh, P("actor"))
        snap = actor_learner.place(snap, mesh, P())
    last_saved = i
    t0 = time.time()
    try:
        while i < iterations:
            if resilience is not None:
                resilience.round_start(i)
            # clip rounds to record boundaries so evals land at the same
            # iteration counts whatever the chunk size.  NB unlike the
            # scan-fused driver the PRNG chain here is per-ROUND (one
            # split serves the whole chunk), so different steps_per_call
            # values are different — equally valid — trajectories; only
            # the barrier anchor mode at steps_per_call=1 is
            # bitwise-pinned to the synchronous topology
            next_stop = min((i // record_every + 1) * record_every,
                            iterations)
            c = min(max(steps_per_call, 1), next_stop - i)
            k_run, k_it = jax.random.split(k_run)
            k_roll, k_up = jax.random.split(k_it)
            if barrier:
                wbuf = learner.extras.replay
            with jax.profiler.TraceAnnotation("train.chunk"):
                env_state, obs, wbuf, _ = progs.actor_chunk(
                    snap, env_state, obs, wbuf, k_roll, n_chunks=c)
                if barrier:
                    learner = learner._replace(
                        extras=learner.extras._replace(replay=wbuf))
                learner, l_m = progs.learner_chunk(
                    learner, k_up, n_updates=c * cfg.updates_per_iter)
            total_updates += c * cfg.updates_per_iter
            updates_since_push += c * cfg.updates_per_iter
            i += c
            if resilience is not None:
                # nan_grad target + finite guard on the learner (the
                # one host sync a guarded async run adds per round)
                learner = resilience.after_round(
                    learner, i,
                    learner_view=lambda s: s.params,
                    set_learner=lambda s, p: s._replace(params=p))
            if updates_since_push >= sync_every and (
                    resilience is None or resilience.sync_due(i)):
                if not barrier:
                    learner, wbuf = actor_learner.swap_read_slot(learner,
                                                                 wbuf)
                actor_lags.append(total_updates - snap_minted_at)
                if resilience is not None:
                    # guarded push: bitflip_push lands here, the CRC +
                    # structural verify catches it, and a corrupted
                    # payload is re-minted (bounded backoff) before it
                    # can reach the actors
                    snap = resilience.push(
                        functools.partial(progs.make_snapshot, learner,
                                          obs), i)
                else:
                    snap = progs.make_snapshot(learner, obs)
                snap_minted_at = total_updates
                div_futs.append(progs.divergence(learner, snap, obs))
                updates_since_push = 0
            if i % record_every == 0 or i == iterations:
                with jax.profiler.TraceAnnotation("train.eval"):
                    r, k_run = evaluate_at(
                        (learner.params, learner.observers, learner.step),
                        obs, k_run, i)
                rewards.append(r)
                losses.append(float(l_m["loss"]))
                # neither async program surfaces an action-variance
                # metric (same zeros the synchronous actor-learner
                # topology records)
                variances.append(0.0)
            if ckptr is not None and checkpoint_every > 0 and (
                    i - last_saved >= checkpoint_every or
                    (i == iterations and i > last_saved)):
                # saves land at natural round boundaries only (cadence
                # never clips a round), so the per-round PRNG chain —
                # and with it the whole trajectory — is identical with
                # or without checkpointing.  Host-copying here blocks
                # this thread on the in-flight chunks, but never inserts
                # a device barrier into the dispatch chain itself.
                with jax.profiler.TraceAnnotation("train.checkpoint"):
                    div_futs = [np.asarray(d) for d in div_futs]
                    ckptr.save_async(
                        i, {"learner": learner,
                            "wbuf": None if barrier else wbuf,
                            "env_state": env_state, "obs": obs, "snap": snap,
                            "key": k_run},
                        extra={"iteration": i, "rewards": rewards,
                               "action_variances": variances,
                               "divergences": [d.tolist()
                                           for d in div_futs],
                               "actor_lags": actor_lags, "losses": losses,
                               "updates_since_push": updates_since_push,
                               "total_updates": total_updates,
                               "snap_minted_at": snap_minted_at})
                last_saved = i
                if resilience is not None:
                    resilience.checkpoint_committed(ckptr, i)
        wall = time.time() - t0
        divergences = [np.asarray(d).tolist() for d in div_futs]
        if ckptr is not None:
            ckptr.wait()
    finally:
        # never leak the writer thread past a fault/guard error — the
        # supervisor's next attempt opens a fresh checkpointer
        if ckptr is not None:
            ckptr.close()
    return TrainResult(state=learner, act_fn=progs.act_fn, env=env,
                       rewards=rewards, action_variances=variances,
                       wall_time_s=wall, algo_cfg=cfg, net=net,
                       divergences=divergences, actor_lags=actor_lags,
                       losses=losses, env_state=env_state)


@functools.lru_cache(maxsize=32)
def _det_act(act_fn):
    """Deterministic wrapper with a cached identity per underlying act_fn.

    Threads (params, observers, step) through ``evaluate``'s params slot so
    repeated evals of one trained policy (e.g. the ``quarl_ptq`` bits loop)
    reuse a single compiled eval program.
    """
    return lambda p, o: act_fn(p[0], o, p[1], p[2])


def eval_policy(result: TrainResult, quant: QuantConfig, key,
                episodes: int = 16, *, actor_backend: str = "fp32",
                kernel_backend: str = "auto") -> float:
    """Eval(Q(M)) — run the (possibly quantized) policy deterministically.

    Deployment quantizes only the actor: ``result.state.params`` holds the
    actor params for every algorithm (the DDPG critic lives in
    ``state.extras`` and never runs at deployment, per the paper).

    ``actor_backend="int8"`` deploys the packed int8 actor through the W8A8
    kernel (``kernels.ops.int8_matmul``, ``kernel_backend`` selecting
    pallas/interpret/ref/xla/auto) for int PTQ configs of <= 8 bits;
    ``"int4"`` additionally caps the packed width at 4 bits (byte-packed
    W4A8 — the half-size deployment cache); other configs (fp16, wide
    ints, QAT range replay) keep the fp32 simulation.
    """
    actorq.validate_actor_backend(actor_backend)
    if (actorq.is_quantized(actor_backend)
            and quant.mode == QuantMode.PTQ_INT and quant.bits <= 8):
        bits = min(quant.bits, actorq.backend_bits(actor_backend))
        qparams = actorq.pack_actor_params(result.state.params, bits=bits)
        act = actorq.make_act_fn(result.env.spec, backend=kernel_backend)
        return float(evaluate(result.env, act, qparams, key, episodes,
                              max_steps=result.env.spec.max_steps))
    params = common.eval_params(result.state.params, quant)
    return float(evaluate(
        result.env, _det_act(result.act_fn),
        (params, result.state.observers, result.state.step), key, episodes,
        max_steps=result.env.spec.max_steps))


@dataclasses.dataclass
class QuarlResult:
    """One row of a QuaRL PTQ/QAT study: fp32 vs quantized eval reward
    for (``algo``, ``env``) at the bit-width named by ``label``, with the
    paper's relative ``error_pct`` and study-specific ``extra`` values."""

    algo: str
    env: str
    label: str
    fp32_reward: float
    quant_reward: float
    error_pct: float
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)


def quarl_ptq(algo: str, env_name: str, bits_list=(8, 16), *,
              iterations: int = 200, seed: int = 0,
              net_kwargs=None, algo_overrides=None,
              eval_episodes: int = 16, steps_per_call: int = 1,
              actor_backend: str = "fp32") -> List[QuarlResult]:
    """Algorithm 1 over fp16 + intN PTQ.

    ``actor_backend="int8"`` deploys each intN evaluation through the packed
    int8 actor instead of the fp32 fake-quant simulation (the fp32 baseline
    eval always runs fp32).
    """
    result = train(algo, env_name, iterations=iterations, seed=seed,
                   net_kwargs=net_kwargs, algo_overrides=algo_overrides,
                   steps_per_call=steps_per_call)
    key = jax.random.PRNGKey(seed + 1000)
    fp32 = eval_policy(result, QuantConfig.none(), key, eval_episodes)
    out = []
    for bits in bits_list:
        q = QuantConfig.ptq_fp16() if bits == 16 else QuantConfig.ptq_int(bits)
        r = eval_policy(result, q, key, eval_episodes,
                        actor_backend=actor_backend)
        out.append(QuarlResult(
            algo=algo, env=env_name, label=q.label(), fp32_reward=fp32,
            quant_reward=r,
            error_pct=metrics_lib.relative_error(fp32, r),
            extra={"weight_stats": metrics_lib.weight_distribution_stats(
                result.state.params)}))
    return out


def quarl_qat(algo: str, env_name: str, bits: int, *, iterations: int = 200,
              quant_delay_frac: float = 0.5, seed: int = 0,
              net_kwargs=None, algo_overrides=None,
              eval_episodes: int = 16, steps_per_call: int = 1,
              actor_backend: str = "fp32") -> QuarlResult:
    """Algorithm 2: train with fake quantization after a monitoring delay.

    ``actor_backend="int8"`` collects the QAT run's rollouts with the true
    int8 actor (A2C/DQN); the QAT evaluation itself replays the monitored
    fake-quant ranges, which need the fp32 simulation path.
    """
    delay = int(iterations * quant_delay_frac)
    quant = QuantConfig.qat(bits, quant_delay=delay)
    fp = train(algo, env_name, iterations=iterations, seed=seed,
               net_kwargs=net_kwargs, algo_overrides=algo_overrides,
               steps_per_call=steps_per_call)
    qt = train(algo, env_name, iterations=iterations, quant=quant,
               seed=seed, net_kwargs=net_kwargs,
               algo_overrides=algo_overrides,
               steps_per_call=steps_per_call, actor_backend=actor_backend)
    key = jax.random.PRNGKey(seed + 2000)
    fp32 = eval_policy(fp, QuantConfig.none(), key, eval_episodes)
    q_r = eval_policy(qt, quant, key, eval_episodes)
    return QuarlResult(
        algo=algo, env=env_name, label=f"qat{bits}", fp32_reward=fp32,
        quant_reward=q_r, error_pct=metrics_lib.relative_error(fp32, q_r),
        extra={"variances_fp": fp.action_variances,
               "variances_qat": qt.action_variances,
               "rewards_fp": fp.rewards, "rewards_qat": qt.rewards})
