"""ActorQ actor–learner topologies: int8 actor fan-out + fp32 replay learner.

The paper's headline system is a distributed training paradigm: a pool of
8-bit quantized *actors* collects experience into a replay buffer while a
full-precision *learner* samples batches and periodically broadcasts
refreshed parameters to the actors.  This module reproduces that topology on
top of the repo's replay algorithms (DQN, DDPG — the paper's DQN/D4PG
analogues) in two flavours:

* ``topology="actor-learner"`` — bulk-synchronous: one jitted iteration
  runs rollout -> replay add -> learner updates -> (cadenced) param push.
* ``topology="async"`` — the overlapped regime the paper's speedups come
  from: the actor phase and the learner phase compile to two *independent*
  jit programs with disjoint state (``make_async_actor_learner``).  Actors
  roll a chunk of rollouts into the **write slot** of a double-buffered
  replay (``buffer.DoubleBuffer``) while the learner drains the **read
  slot**; the host driver (``loops.train(topology="async")``) dispatches
  both programs back-to-back with **no** ``block_until_ready`` between
  them, swaps the slots by host-level reference exchange at sync points,
  and pushes refreshed (int8-packed) params to the actors via a snapshot
  program.  Dispatch overlap on a single host; on a device mesh both
  programs are ``shard_map``-ped over the actor axis as separate XLA
  executables.

Shared mechanics:

* **Actor fan-out** — ``num_actors`` actor replicas, each running
  ``cfg.n_envs`` environments with the behaviour policy of the underlying
  algorithm (``dqn.make_behaviour_policy`` / ``ddpg.make_behaviour_policy``).
  With ``actor_backend="int8"`` the replicas step through the W8A8 kernel
  using a packed int8 param cache that is repacked **only at sync points**
  (carried in ``ActorLearnerState.actor_cache`` under ``lax.cond`` for the
  synchronous topology; minted by the snapshot program for async) — between
  syncs the actor params are unchanged, so repacking would be pure waste.
* **Sharded replay** — each actor owns one shard (``buffer.*_sharded``;
  with ``replay="prioritized"`` every shard carries its own sum-tree);
  the learner samples ``batch_size / num_actors`` per shard and priority
  pushes stay shard-local.  Under async each *slot* of the double buffer
  is such a sharded buffer of half the total capacity.
* **Staleness contract** — measured in *learner updates*: a push refreshes
  the actors every ``sync_every`` learner updates.  The synchronous
  topology performs exactly ``updates_per_iter`` learner updates per
  iteration and pushes on iteration boundaries, so its ``sync_every``
  knob (kept in iterations for backwards compatibility) equals
  ``sync_every * updates_per_iter`` learner updates; the async driver
  takes ``sync_every`` in learner updates directly and records, per sync,
  the retiring snapshot's **actor lag** (how many learner updates it
  served for).  The first push happens after the first ``sync_every``
  period — at init the actors hold a fresh copy by construction, which is
  *not* a sync — and divergence is recorded **only at true pushes**.
* **Divergence metrics** — at every push: per actor, the mean absolute gap
  between the freshly-synced actor behaviour head (int8 under
  ``actor_backend="int8"``) and the fp32 learner head on the actors'
  current observations.  Off the hot path: ``lax.cond`` in the sync
  topology, a separately-dispatched (never-blocked-on) program in async.

Single-actor equivalence: with ``num_actors=1`` and ``sync_every=1`` (no
mesh) the synchronous topology is *bitwise identical* to the fused
``loops.train`` driver for DQN — same PRNG chain, same replay contents,
same updates — and ``topology="async"`` with ``steps_per_call=1``,
``async_barrier=True`` and ``sync_every=updates_per_iter`` reproduces the
synchronous learner trajectory bitwise (the barrier mode threads a single
replay slot actor -> learner, serializing the round by dataflow).  Both
contracts are enforced by ``tests/test_actor_learner.py`` /
``tests/test_async_actor_learner.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.rl import actorq, common, ddpg, dqn
from repro.rl import buffer as rb
from repro.rl.env import Env, batched_env, rollout

ALGOS = ("dqn", "ddpg")
TOPOLOGIES = ("fused", "actor-learner", "async")


def validate_topology(topology: str) -> str:
    if topology not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                         f"got {topology!r}")
    return topology


@dataclasses.dataclass(frozen=True)
class ActorLearnerConfig:
    """Topology knobs (the algorithm's own config rides separately).

    ``sync_every`` is the staleness contract: under ``topology="async"``
    it counts *learner updates* between param pushes; the synchronous
    topology keeps its historical iteration cadence (one iteration =
    ``updates_per_iter`` learner updates, pushes on iteration boundaries).
    """
    num_actors: int = 2
    sync_every: int = 1


class ActorLearnerState(NamedTuple):
    """The bulk-synchronous topology's full carry.

    Checkpoint contract (``repro.checkpoint`` / ``loops.train``
    ``checkpoint_dir``): every field — the learner with its optimizer
    state and sharded replay (uniform or PER sum-trees), the stale actor
    params, the packed int8/int4 ``actor_cache`` (``core.ptq`` registers
    ``PackedTensor`` as a pytree, so the codes/scales flatten like any
    leaf) and the schedule counters — is an array leaf, so the whole
    state round-trips through ``tree_leaves``; re-running ``init`` with
    the same seed/config rebuilds the matching restore template.
    """

    learner: common.TrainState    # fp32 learner; extras.replay is sharded
    actor_params: Any             # the actors' (possibly stale) param copy
    actor_cache: Any              # packed int8 cache of actor_params
    #                               (() under actor_backend="fp32");
    #                               repacked only at sync points
    t: jnp.ndarray                # iterations completed
    divergence: jnp.ndarray       # (num_actors,) actor-vs-learner head gap


class ActorSnapshot(NamedTuple):
    """What the async actor program knows about the learner: the params
    (and their int8 cache) from the last push plus the schedule counters
    frozen at mint time.  Minted by ``AsyncPrograms.make_snapshot`` — a
    plain jit, so every leaf is a fresh buffer that never aliases the
    learner state the next learner chunk donates.

    Checkpointable like ``ActorLearnerState``: the async driver saves the
    live snapshot alongside the learner so a resumed run keeps serving
    the *same* (possibly stale) actor params until the next sync point —
    re-minting on resume would silently skip ahead of the staleness
    schedule and break the bitwise-resume contract."""
    params: Any
    cache: Any                    # packed int8 cache (() for fp32 actors)
    step: jnp.ndarray
    updates: jnp.ndarray          # learner updates landed at mint time


class AsyncPrograms(NamedTuple):
    """The async topology's program set (see ``make_async_actor_learner``).

    ``actor_chunk`` and ``learner_chunk`` are the two overlapping hot-path
    programs; ``make_snapshot`` and ``divergence`` run once per sync and
    are dispatched without ever being blocked on.
    """
    actor_chunk: Callable         # (snap, env_state, obs, wbuf, key,
    #                                *, n_chunks) -> (env_state, obs,
    #                                wbuf, {"reward"})
    learner_chunk: Callable       # (learner, key, *, n_updates)
    #                                -> (learner, {"loss"})
    make_snapshot: Callable       # learner -> ActorSnapshot
    divergence: Callable          # (learner, snap, obs) -> (num_actors,)
    act_fn: Callable              # deterministic eval policy (fp32 head)
    benv_global: Env              # num_actors * n_envs environments


class _AlgoParts(NamedTuple):
    build_policy: Callable        # (params, observers, step, updates,
    #                                cache) -> policy
    learn: Callable               # the algorithm's update part
    fp32_head: Callable           # (params, obs, observers, step) -> head
    cache_head: Callable          # (packed cache, obs) -> behaviour head
    act_fn: Callable              # deterministic eval policy


def _algo_parts(algo: str, env: Env, net, cfg) -> _AlgoParts:
    """Behaviour/learner/head builders shared by both topologies."""
    if algo == "dqn":
        _build = dqn.make_behaviour_policy(env, net, cfg)
        learn = dqn.make_td_update(env, net, cfg)

        def build_policy(params, observers, step, updates, cache):
            return _build(params, observers, step, updates, qparams=cache)

        def fp32_head(params, obs, observers, step):
            return dqn._q_values(net, cfg, params, obs, observers, step)[0]

        def cache_head(cache, obs):
            return actorq.quantized_apply(cache, obs,
                                          backend=cfg.kernel_backend)

        def act_fn(params, obs, observers=None, step=1 << 30):
            q = fp32_head(params, obs, observers or {}, jnp.asarray(step))
            return jnp.argmax(q, axis=-1).astype(jnp.int32)
    else:
        _build = ddpg.make_behaviour_policy(env, net, cfg)
        learn = ddpg.make_update(env, net, cfg)

        def build_policy(params, observers, step, updates, cache):
            return _build(params, observers, step, qparams=cache)

        def fp32_head(params, obs, observers, step):
            return ddpg._actor_out(net, cfg, params, obs, observers,
                                   step)[0]

        def cache_head(cache, obs):
            return jnp.tanh(actorq.quantized_apply(
                cache, obs, backend=cfg.kernel_backend))

        def act_fn(params, obs, observers=None, step=1 << 30):
            a = fp32_head(params, obs, observers or {}, jnp.asarray(step))
            return a * env.spec.action_scale
    return _AlgoParts(build_policy, learn, fp32_head, cache_head, act_fn)


def _validate(algo: str, cfg, al: ActorLearnerConfig, mesh, axis: str):
    if algo not in ALGOS:
        raise ValueError(f"actor-learner supports {ALGOS}, got {algo!r}")
    actorq.validate_actor_backend(cfg.actor_backend)
    if al.sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {al.sync_every}")
    n = al.num_actors
    n_dev = mesh.shape[axis] if mesh is not None else 1
    if n % n_dev:
        raise ValueError(f"num_actors {n} must divide by the mesh "
                         f"{axis!r} axis size {n_dev}")
    if cfg.batch_size % n:
        raise ValueError(f"batch_size {cfg.batch_size} must divide by "
                         f"num_actors {n}")
    return n, n_dev


def _make_to_shards(local_actors: int, envs_per_actor: int):
    """(T, local_actors * envs_per_actor, ...) rollout leaves -> per-shard
    (local_actors, T * envs_per_actor, ...) batches (actor-major)."""
    def to_shards(x):
        t_dim, trail = x.shape[0], x.shape[2:]
        y = x.reshape((t_dim, local_actors, envs_per_actor) + trail)
        y = jnp.moveaxis(y, 1, 0)
        return y.reshape((local_actors, t_dim * envs_per_actor) + trail)
    return to_shards


def _make_learner_phase(parts: _AlgoParts, cfg, use_per: bool,
                        per_actor_batch: int, local_actors: int):
    """``learner_phase(learner, key, total_size, n_updates, reduce)`` —
    the scan of per-shard sample -> fp32 update (-> priority push) steps
    shared by the synchronous core and the async learner program."""
    learn = parts.learn

    def learner_phase(learner, k_updates, total_size, n_updates, reduce):
        @common.phase("learner_update")
        def one_update(st, k):
            keys_a = k[None] if local_actors == 1 \
                else jax.random.split(k, local_actors)
            if use_per:
                # same anneal schedule as the fused drivers
                # (common.per_beta, on the learner-update counter);
                # priority pushes stay per-shard, inside the shard_map —
                # the actor axis never gathers
                beta = common.per_beta(st, cfg)
                with common.phase("replay_sample"):
                    shards, idx, w = rb.per_sample_sharded(
                        st.extras.replay, keys_a, per_actor_batch, beta)
                    batch = jax.tree_util.tree_map(
                        lambda x: x.reshape((-1,) + x.shape[2:]), shards)
                st, (loss, td_abs) = learn(st, batch, total_size,
                                           weights=w.reshape(-1),
                                           reduce=reduce)
                with common.phase("replay_sample"):
                    per = rb.per_update_priorities_sharded(
                        st.extras.replay, idx, td_abs.reshape(idx.shape),
                        cfg.priority_exponent)
                st = st._replace(extras=st.extras._replace(replay=per))
                return st, loss
            with common.phase("replay_sample"):
                shards = rb.replay_sample_sharded(st.extras.replay, keys_a,
                                                  per_actor_batch)
                batch = jax.tree_util.tree_map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), shards)
            st, (loss, _) = learn(st, batch, total_size, reduce=reduce)
            return st, loss

        learner, losses = jax.lax.scan(
            one_update, learner, jax.random.split(k_updates, n_updates))
        return learner, losses
    return learner_phase


def _make_divergence(parts: _AlgoParts, int8: bool, n_actors: int,
                     envs_per_actor: int, obs_shape):
    """``divergence(learner, actor_params, cache, obs) -> (n_actors,)`` —
    per-actor mean-abs gap between the actors' behaviour head (the packed
    cache under int8, the stale params otherwise) and the live fp32
    learner head, shared by both topologies."""
    def divergence(learner, actor_params, cache, obs):
        obs_a = obs.reshape((n_actors, envs_per_actor) + obs_shape)

        def one(o):
            fresh = parts.fp32_head(learner.params, o, learner.observers,
                                    learner.step)
            if int8:
                behaved = parts.cache_head(cache, o)
            else:
                behaved = parts.fp32_head(actor_params, o,
                                          learner.observers, learner.step)
            return jnp.mean(jnp.abs(behaved - fresh))
        return jax.vmap(one)(obs_a)
    return divergence


def _sharded_init(algo: str, env: Env, cfg):
    """Per-discipline sharded slot initializer for one algorithm."""
    init_sharded = rb.per_init_sharded \
        if rb.use_prioritized(cfg.replay, cfg.priority_exponent) \
        else rb.replay_init_sharded

    def make_slot(n_shards: int, capacity: int):
        if algo == "ddpg":
            return init_sharded(n_shards, capacity, env.spec.obs_shape,
                                action_shape=(env.spec.action_dim,),
                                action_dtype=jnp.float32)
        return init_sharded(n_shards, capacity, env.spec.obs_shape)
    return make_slot


def init(key, env: Env, net, algo: str, cfg, al: ActorLearnerConfig
         ) -> ActorLearnerState:
    """Learner state + actor copy (+ int8 cache) + sharded replay.

    ``net``/``cfg`` are the underlying algorithm's network(s) and config
    (``dqn.DQNConfig`` / ``ddpg.DDPGConfig``).  The algorithm's fused
    replay is swapped for the sharded layout (total capacity conserved:
    ``buffer_size / num_actors`` per shard).  The actor copy is a real
    copy, not an alias — the scan-fused driver donates the whole state and
    donation rejects one buffer appearing twice.
    """
    if algo not in ALGOS:
        raise ValueError(f"actor-learner supports {ALGOS}, got {algo!r}")
    n = al.num_actors
    if n < 1 or cfg.buffer_size % n:
        raise ValueError(f"buffer_size {cfg.buffer_size} must divide by "
                         f"num_actors {n}")
    mod = {"dqn": dqn, "ddpg": ddpg}[algo]
    state = mod.init(key, env, net, cfg)
    sharded = _sharded_init(algo, env, cfg)(n, cfg.buffer_size // n)
    state = state._replace(extras=state.extras._replace(replay=sharded))
    actor_params = jax.tree_util.tree_map(jnp.array, state.params)
    # the packed cache keeps fp32 leaves (biases) by reference — copy them
    # so the scan-fused driver's donated state holds no buffer twice.
    # calib_batch: the t=0 cache calibrates from fresh env-reset
    # observations (no rollout data exists yet); every later refresh
    # recalibrates from the live actor observations at the sync point.
    cache = ()
    if actorq.is_quantized(cfg.actor_backend):
        calib_obs = None
        if cfg.calib_batch:
            _, calib_obs = batched_env(env, max(cfg.calib_batch, 1)).reset(
                jax.random.fold_in(key, 0x5CA1E))
        cache = jax.tree_util.tree_map(
            jnp.array, actorq.make_actor_cache(
                actor_params, cfg.actor_backend, calib_obs=calib_obs,
                backend=cfg.kernel_backend))
    return ActorLearnerState(
        learner=state, actor_params=actor_params, actor_cache=cache,
        t=jnp.zeros((), jnp.int32),
        divergence=jnp.zeros((al.num_actors,), jnp.float32))


def init_async(key, env: Env, net, algo: str, cfg, al: ActorLearnerConfig,
               *, double: bool = True):
    """``(learner_state, write_slot)`` for the async topology.

    The learner state carries the **read slot** in ``extras.replay``; the
    returned ``write_slot`` is the actors' independent slot (each of
    capacity ``buffer_size / (2 * num_actors)`` per shard, conserving the
    total).  With ``double=False`` (the ``async_barrier`` equivalence
    mode) there is a single slot of the synchronous topology's capacity
    and ``write_slot`` is ``None`` — the driver threads
    ``learner.extras.replay`` through the actor program instead.
    """
    if algo not in ALGOS:
        raise ValueError(f"actor-learner supports {ALGOS}, got {algo!r}")
    n = al.num_actors
    slots = 2 if double else 1
    if n < 1 or cfg.buffer_size % (n * slots):
        raise ValueError(
            f"buffer_size {cfg.buffer_size} must divide by num_actors x "
            f"slots = {n} x {slots} (double-buffered async replay)")
    mod = {"dqn": dqn, "ddpg": ddpg}[algo]
    state = mod.init(key, env, net, cfg)
    make_slot = _sharded_init(algo, env, cfg)
    cap = cfg.buffer_size // (n * slots)
    if double:
        db = rb.double_buffer_init(make_slot, n, cap)
        read, write = db.read, db.write
    else:
        read, write = make_slot(n, cap), None
    state = state._replace(extras=state.extras._replace(replay=read))
    return state, write


def swap_read_slot(learner: common.TrainState, wbuf):
    """Sync-point slot swap for the async topology.

    The learner carries the read slot in ``extras.replay``; this applies
    ``buffer.double_buffer_swap`` to the (read, write) pair — the freshly
    written slot becomes the learner's next read slot, the drained slot
    becomes the actors' next write slot.  Pure host-level reference
    exchange between (possibly in-flight) futures: no device op, no
    synchronization.  Returns ``(learner, wbuf)`` with the roles traded.
    """
    db = rb.double_buffer_swap(
        rb.DoubleBuffer(read=learner.extras.replay, write=wbuf))
    learner = learner._replace(
        extras=learner.extras._replace(replay=db.read))
    return learner, db.write


def with_cache(state: ActorLearnerState, cache) -> ActorLearnerState:
    """Swap the packed actor cache — the resilience corruption/repair seam.

    ``repro.resilience`` targets the in-state cache for ``bitflip_push``
    faults (and restores a verified re-mint after a guard trips) through
    this helper rather than reaching into the NamedTuple, so the state
    shape stays a private detail of this module.
    """
    return state._replace(actor_cache=cache)


def remint_cache(state: ActorLearnerState, actor_backend: str, *,
                 kernel_backend: str = "auto"):
    """Deterministically re-mint the packed cache from the stale params.

    The integrity reference for ``repro.resilience.guards``: under
    ``calib_batch == 0`` the in-jit sync-point repack is a pure function
    of ``state.actor_params``, so a host-side re-mint reproduces it
    bitwise (the repo's standing eager-vs-jit CPU parity anchor) and a
    CRC mismatch against the carried cache means corruption, not drift.
    Returns ``()`` untouched for fp32 actors.  With calibration enabled
    the repack consumes live rollout observations that no longer exist
    host-side, so there is no deterministic reference — callers skip
    verification in that regime (``loops._guard_round``).
    """
    if state.actor_cache == () or not actorq.is_quantized(actor_backend):
        return ()
    return actorq.make_actor_cache(state.actor_params, actor_backend,
                                   backend=kernel_backend)


def mesh_specs(tree, axis: str = "actor"):
    """Partition specs for a topology carry (``ActorLearnerState`` or a bare
    learner ``TrainState``): replay (and divergence) leaves live on the
    actor axis, everything else (learner params/opt, actor copy + cache)
    is replicated.  The programs' ``shard_map`` specs and ``place`` both
    come from here, so a placed carry is exactly what the programs take.
    """
    def one(path, leaf):
        names = {getattr(entry, "name", None) for entry in path}
        sharded = "replay" in names or "divergence" in names
        return P(axis) if sharded else P()
    return jax.tree_util.tree_map_with_path(one, tree)


def place(tree, mesh, specs):
    """Commit ``tree`` to ``mesh`` under ``specs`` (a matching pytree, or a
    prefix of one, of ``PartitionSpec``).

    Call once on every carry before the first sharded program: arrays made
    off the mesh sit on one device, and a donated input whose sharding
    differs from its output's cannot alias it.  Placed arrays keep their
    shardings through the programs, so later calls move nothing.
    """
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    return jax.device_put(tree, shardings)


def make_actor_learner(algo: str, env: Env, net, cfg,
                       al: ActorLearnerConfig, mesh=None,
                       axis: str = "actor"):
    """Returns ``(iteration, act_fn, benv_global)`` — the bulk-synchronous
    topology.

    ``iteration(state, env_state, obs, key) -> (state, env_state, obs,
    metrics)`` — the same contract as the fused algorithms, so the
    scan-fused driver (``loops.make_scan_iteration``) and ``loops.train``
    drive it unchanged.  ``benv_global`` batches
    ``num_actors * cfg.n_envs`` environments (actor-major layout).

    With ``mesh`` given, the actor axis is ``shard_map``-ped over
    ``mesh.shape[axis]`` devices (``num_actors`` must divide by it; each
    device runs ``num_actors / n_dev`` replicas) and learner gradients are
    ``pmean``-averaged.  Without a mesh the replicas run as one vectorized
    batch on the local device.
    """
    use_per = rb.use_prioritized(cfg.replay, cfg.priority_exponent)
    n, n_dev = _validate(algo, cfg, al, mesh, axis)
    local_actors = n // n_dev
    envs_per_actor = cfg.n_envs
    per_actor_batch = cfg.batch_size // n
    # sequence nets with a quantized backend carry per-env KV-cache actor
    # state inside the env state (local and global wraps must agree so the
    # shard_map P(axis) specs see the same batch-leading tree structure)
    benv_local = actorq.maybe_attach_seq_state(
        batched_env(env, local_actors * envs_per_actor), net,
        cfg.actor_backend, local_actors * envs_per_actor)
    benv_global = actorq.maybe_attach_seq_state(
        batched_env(env, n * envs_per_actor), net, cfg.actor_backend,
        n * envs_per_actor)
    obs_shape = tuple(env.spec.obs_shape)
    int8 = actorq.is_quantized(cfg.actor_backend)

    parts = _algo_parts(algo, env, net, cfg)
    learner_phase = _make_learner_phase(parts, cfg, use_per,
                                        per_actor_batch, local_actors)
    to_shards = _make_to_shards(local_actors, envs_per_actor)
    add_sharded = rb.per_add_sharded if use_per else rb.replay_add_sharded

    divergence = _make_divergence(parts, int8, local_actors,
                                  envs_per_actor, obs_shape)

    def core(state: ActorLearnerState, env_state, obs, key, axis_name):
        if axis_name is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
            reduce = functools.partial(jax.lax.pmean, axis_name=axis_name)
        else:
            def reduce(x):
                return x
        learner, actor_params = state.learner, state.actor_params
        k_roll, k_updates = jax.random.split(key)

        # --- actor phase: stale-param rollouts into the local shards -----
        # (int8: the cache packed at the last sync, carried in state)
        policy = parts.build_policy(actor_params, learner.observers,
                                    learner.step, learner.extras.updates,
                                    state.actor_cache if int8 else None)
        env_state, obs, traj = rollout(
            benv_local, policy, actor_params, env_state, obs, k_roll,
            cfg.rollout_steps)

        with common.phase("replay_insert"):
            flat = jax.tree_util.tree_map(to_shards, traj)
            replay = add_sharded(
                learner.extras.replay,
                rb.Transition(flat.obs, flat.action, flat.reward, flat.done,
                              flat.next_obs))
            learner = learner._replace(
                extras=learner.extras._replace(replay=replay))
            total_size = rb.replay_total_size(replay)
            if axis_name is not None:
                total_size = jax.lax.psum(total_size, axis_name)

        # --- learner phase: per-shard sampling, fp32 updates -------------
        learner, losses = learner_phase(learner, k_updates, total_size,
                                        cfg.updates_per_iter, reduce)

        # --- sync phase: staleness contract + divergence metric -----------
        # first push at t == sync_every (t=0 is init, where the actors hold
        # a fresh copy by construction — not a sync, and not a divergence
        # sample); between pushes actors run the stale params + stale cache
        with common.phase("param_push"):
            t = state.t + 1
            do_sync = (t % al.sync_every) == 0
            actor_params = jax.tree_util.tree_map(
                lambda a, p: jnp.where(do_sync, p, a), actor_params,
                learner.params)
            if int8:
                # repack the int cache only at true pushes — between syncs
                # the actor params are unchanged and the cache is
                # bitwise-stable.  calib_batch: the repack also refreshes
                # the static activation scales from the actors' current
                # observations, so the fused kernel's requant ranges track
                # the data distribution at the same cadence as the params.
                def repack(p):
                    calib_obs = None
                    if cfg.calib_batch:
                        # the cache is carried replicated over the actor
                        # axis (P() in mesh_specs): on a mesh, gather the
                        # calibration batch so every device derives
                        # identical scales (collective only inside the
                        # sync branch)
                        calib_obs = obs if axis_name is None else \
                            jax.lax.all_gather(obs, axis_name, axis=0,
                                               tiled=True)
                        calib_obs = actorq.calib_slice(calib_obs,
                                                       cfg.calib_batch)
                    return actorq.make_actor_cache(
                        p, cfg.actor_backend, calib_obs=calib_obs,
                        backend=cfg.kernel_backend)

                cache = jax.lax.cond(
                    do_sync,
                    repack,
                    lambda _: state.actor_cache,
                    actor_params)
            else:
                cache = state.actor_cache
            # divergence is recorded at sync points only (lax.cond keeps
            # the extra head passes off the non-sync iterations); between
            # syncs the last recorded value carries through
            div = jax.lax.cond(
                do_sync,
                lambda args: divergence(*args),
                lambda args: state.divergence,
                (learner, actor_params, cache, obs))

        reward = jnp.sum(traj.reward) / jnp.maximum(jnp.sum(traj.done),
                                                    1.0)
        loss = jnp.mean(losses)
        if axis_name is not None:
            reward = jax.lax.pmean(reward, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
        metrics = {"loss": loss, "reward": reward, "divergence": div,
                   "synced": do_sync}
        new_state = ActorLearnerState(learner, actor_params, cache, t, div)
        return new_state, env_state, obs, metrics

    if mesh is None:
        @jax.jit
        def iteration(state, env_state, obs, key):
            return core(state, env_state, obs, key, None)
    else:
        @jax.jit
        def iteration(state, env_state, obs, key):
            specs = mesh_specs(state, axis)
            metric_specs = {"loss": P(), "reward": P(),
                            "divergence": P(axis), "synced": P()}
            sharded = jax.shard_map(
                functools.partial(core, axis_name=axis), mesh=mesh,
                in_specs=(specs, P(axis), P(axis), P()),
                out_specs=(specs, P(axis), P(axis), metric_specs),
                check_vma=False)
            return sharded(state, env_state, obs, key)

    return iteration, parts.act_fn, benv_global


def make_async_actor_learner(algo: str, env: Env, net, cfg,
                             al: ActorLearnerConfig, mesh=None,
                             axis: str = "actor") -> AsyncPrograms:
    """The async topology's program set (``topology="async"``).

    Two independent hot-path programs with disjoint state:

    * ``actor_chunk(snap, env_state, obs, wbuf, key, *, n_chunks)`` —
      ``n_chunks`` rollouts of ``cfg.rollout_steps`` with the snapshot's
      (stale, int8-packed) params, appended to the write slot.  Donates
      ``(env_state, obs, wbuf)``.
    * ``learner_chunk(learner, key, *, n_updates)`` — ``n_updates``
      per-shard sample -> fp32 update (-> priority push) steps against the
      read slot carried in ``learner.extras.replay``.  Donates the learner
      state.

    Because the two programs share no buffers, the host can dispatch both
    for a round and immediately continue — JAX's async dispatch queues
    them with no ``block_until_ready`` barrier; the only cross-program
    edges are the host-level slot swap and the param snapshot at sync
    points.  ``make_snapshot`` packs the int8 cache (the only repack per
    sync) and, being a plain jit, returns fresh buffers that never alias
    the donated learner state.  ``divergence(learner, snap, obs)`` is the
    per-sync ``(num_actors,)`` mean-abs gap of a fresh snapshot's
    behaviour head against the live learner head.  With ``mesh``, every
    program is ``shard_map``-ped over the actor axis (learner grads
    pmean-averaged; the slots' shard axis partitioned; the snapshot
    replicated) as separate XLA executables.
    """
    use_per = rb.use_prioritized(cfg.replay, cfg.priority_exponent)
    n, n_dev = _validate(algo, cfg, al, mesh, axis)
    local_actors = n // n_dev
    envs_per_actor = cfg.n_envs
    per_actor_batch = cfg.batch_size // n
    benv_local = actorq.maybe_attach_seq_state(
        batched_env(env, local_actors * envs_per_actor), net,
        cfg.actor_backend, local_actors * envs_per_actor)
    benv_global = actorq.maybe_attach_seq_state(
        batched_env(env, n * envs_per_actor), net, cfg.actor_backend,
        n * envs_per_actor)
    obs_shape = tuple(env.spec.obs_shape)
    int8 = actorq.is_quantized(cfg.actor_backend)

    parts = _algo_parts(algo, env, net, cfg)
    learner_phase = _make_learner_phase(parts, cfg, use_per,
                                        per_actor_batch, local_actors)
    to_shards = _make_to_shards(local_actors, envs_per_actor)
    add_sharded = rb.per_add_sharded if use_per else rb.replay_add_sharded

    @common.phase("param_push")
    def snapshot_core(learner: common.TrainState, obs,
                      axis_name) -> ActorSnapshot:
        """Param push: mint the actors' next (packed) snapshot.  ``obs``
        (the actors' current observations) is consumed only under
        ``calib_batch > 0``, where each push also recalibrates the cache's
        static activation scales."""
        cache = ()
        if int8:
            calib_obs = None
            if cfg.calib_batch:
                if obs is None:
                    raise ValueError(
                        "calib_batch > 0 needs the actors' observations "
                        "at every snapshot — pass make_snapshot(learner, "
                        "obs)")
                if axis_name is not None:
                    # the snapshot is replicated: every device calibrates
                    # on the same gathered batch, the one the unsharded
                    # program would take
                    obs = jax.lax.all_gather(obs, axis_name, axis=0,
                                             tiled=True)
                calib_obs = actorq.calib_slice(obs, cfg.calib_batch)
            cache = actorq.make_actor_cache(
                learner.params, cfg.actor_backend, calib_obs=calib_obs,
                backend=cfg.kernel_backend)
        return ActorSnapshot(params=learner.params, cache=cache,
                             step=learner.step,
                             updates=learner.extras.updates)

    def actor_core(snap, env_state, obs, wbuf, key, n_chunks, axis_name):
        if axis_name is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
        policy = parts.build_policy(snap.params, {}, snap.step,
                                    snap.updates,
                                    snap.cache if int8 else None)

        def body(carry, k):
            env_state, obs, wbuf = carry
            env_state, obs, traj = rollout(
                benv_local, policy, snap.params, env_state, obs, k,
                cfg.rollout_steps)
            with common.phase("replay_insert"):
                flat = jax.tree_util.tree_map(to_shards, traj)
                wbuf = add_sharded(
                    wbuf, rb.Transition(flat.obs, flat.action, flat.reward,
                                        flat.done, flat.next_obs))
            r = jnp.sum(traj.reward) / jnp.maximum(jnp.sum(traj.done), 1.0)
            return (env_state, obs, wbuf), r

        keys = key[None] if n_chunks == 1 \
            else jax.random.split(key, n_chunks)
        (env_state, obs, wbuf), rewards = jax.lax.scan(
            body, (env_state, obs, wbuf), keys)
        reward = jnp.mean(rewards)
        if axis_name is not None:
            reward = jax.lax.pmean(reward, axis_name)
        return env_state, obs, wbuf, {"reward": reward}

    def learner_core(learner, key, n_updates, axis_name):
        if axis_name is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
            reduce = functools.partial(jax.lax.pmean, axis_name=axis_name)
        else:
            def reduce(x):
                return x
        total_size = rb.replay_total_size(learner.extras.replay)
        if axis_name is not None:
            total_size = jax.lax.psum(total_size, axis_name)
        learner, losses = learner_phase(learner, key, total_size,
                                        n_updates, reduce)
        loss = jnp.mean(losses)
        if axis_name is not None:
            loss = jax.lax.pmean(loss, axis_name)
        return learner, {"loss": loss}

    _div = _make_divergence(parts, int8, local_actors, envs_per_actor,
                            obs_shape)

    @common.phase("param_push")
    def divergence_core(learner, snap, obs):
        return _div(learner, snap.params, snap.cache, obs)

    if mesh is None:
        @functools.partial(jax.jit, static_argnames=("n_chunks",),
                           donate_argnums=(1, 2, 3))
        def actor_chunk(snap, env_state, obs, wbuf, key, *, n_chunks):
            return actor_core(snap, env_state, obs, wbuf, key, n_chunks,
                              None)

        @functools.partial(jax.jit, static_argnames=("n_updates",),
                           donate_argnums=(0,))
        def learner_chunk(learner, key, *, n_updates):
            return learner_core(learner, key, n_updates, None)

        @jax.jit
        def make_snapshot(learner, obs=None):
            return snapshot_core(learner, obs, None)

        divergence = jax.jit(divergence_core)
    else:
        # every program runs inside a shard_map: the Pallas kernels of the
        # quantized actor cannot be partitioned by the compiler
        @functools.partial(jax.jit, static_argnames=("n_chunks",),
                           donate_argnums=(1, 2, 3))
        def actor_chunk(snap, env_state, obs, wbuf, key, *, n_chunks):
            sharded = jax.shard_map(
                functools.partial(actor_core, n_chunks=n_chunks,
                                  axis_name=axis),
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(axis), P()),
                out_specs=(P(axis), P(axis), P(axis), {"reward": P()}),
                check_vma=False)
            return sharded(snap, env_state, obs, wbuf, key)

        @functools.partial(jax.jit, static_argnames=("n_updates",),
                           donate_argnums=(0,))
        def learner_chunk(learner, key, *, n_updates):
            specs = mesh_specs(learner, axis)
            sharded = jax.shard_map(
                functools.partial(learner_core, n_updates=n_updates,
                                  axis_name=axis),
                mesh=mesh,
                in_specs=(specs, P()),
                out_specs=(specs, {"loss": P()}), check_vma=False)
            return sharded(learner, key)

        @jax.jit
        def make_snapshot(learner, obs=None):
            sharded = jax.shard_map(
                functools.partial(snapshot_core, axis_name=axis),
                mesh=mesh, in_specs=(mesh_specs(learner, axis), P(axis)),
                out_specs=P(), check_vma=False)
            return sharded(learner, obs)

        @jax.jit
        def divergence(learner, snap, obs):
            sharded = jax.shard_map(
                divergence_core, mesh=mesh,
                in_specs=(mesh_specs(learner, axis), P(), P(axis)),
                out_specs=P(axis), check_vma=False)
            return sharded(learner, snap, obs)

    return AsyncPrograms(actor_chunk=actor_chunk,
                         learner_chunk=learner_chunk,
                         make_snapshot=make_snapshot,
                         divergence=divergence,
                         act_fn=parts.act_fn,
                         benv_global=benv_global)
