"""Phase scopes and operator spans.

* every phase of ``common.PHASES`` names operations in the compiled
  actor-learner chunk (int8 MLP, int8 conv, prioritized replay) and in the
  fused DQN/DDPG iterations;
* the scopes change nothing in the compiled program but op metadata;
* ``loops.train`` writes its ``train.chunk``, ``train.eval`` and
  ``train.checkpoint`` host spans into a profiler trace, in both the
  synchronous and the async driver.
"""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import pytest

from repro.rl import actor_learner, common, ddpg, dqn, loops
from repro.rl.envs import make as make_env
from repro.rl.networks import make_network

TINY = dict(n_envs=4, rollout_steps=2, updates_per_iter=2, buffer_size=256,
            batch_size=8, warmup=4)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")


def _actor_learner_text(env_name, net_kwargs, **overrides):
    """Compiled HLO text of a two-iteration actor-learner chunk."""
    env = make_env(env_name)
    net = make_network(env.spec.obs_shape, env.spec.n_actions, **net_kwargs)
    cfg = dataclasses.replace(dqn.DQNConfig(**TINY), **overrides)
    al = actor_learner.ActorLearnerConfig(num_actors=2, sync_every=1)
    state = actor_learner.init(jax.random.PRNGKey(0), env, net, "dqn", cfg,
                               al)
    iteration, _, benv = actor_learner.make_actor_learner("dqn", env, net,
                                                          cfg, al)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    chunk = loops.make_scan_iteration(iteration, 2)
    return chunk.lower(state, env_state, obs,
                       jax.random.PRNGKey(2)).compile().as_text()


def _fused_text(algo):
    """Compiled HLO text of one fused DQN or DDPG iteration (int8 actor)."""
    if algo == "dqn":
        env = make_env("cartpole")
        net = make_network(env.spec.obs_shape, env.spec.n_actions)
        cfg = dqn.DQNConfig(actor_backend="int8", **TINY)
        mod = dqn
    else:
        env = make_env("pendulum")
        net = ddpg.make_nets(env)
        cfg = ddpg.DDPGConfig(actor_backend="int8", **TINY)
        mod = ddpg
    state = mod.init(jax.random.PRNGKey(0), env, net, cfg)
    iteration, _, benv = mod.make_iteration(env, net, cfg)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    return iteration.lower(state, env_state, obs,
                           jax.random.PRNGKey(2)).compile().as_text()


def _program(text):
    """Compiled HLO text without its debug info: op metadata and the
    tables of source files, functions and stack frames it starts with."""
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return _METADATA.sub("", "\n".join(lines[:1] + lines[start:]))


@contextlib.contextmanager
def _no_scope(name):
    """``common.phase`` with nothing traced under a name."""
    yield


def _phases_named(text):
    """The phases that some operation's ``op_name`` names, bare or inside
    a transformation (``jvp(replay_sample)``)."""
    found = set()
    for op_name in _OP_NAME.findall(text):
        for comp in op_name.split("/"):
            for word in re.findall(r"[\w.-]+", comp):
                if word in common.PHASES:
                    found.add(word)
    return found


@pytest.mark.parametrize("env_name,net_kwargs,overrides", [
    ("airnav", dict(hidden=(32, 32)),
     dict(actor_backend="int8", calib_batch=8)),
    ("catch", dict(conv_filters=(8, 8), fc_width=16),
     dict(actor_backend="int8")),
    ("cartpole", {}, dict(replay="prioritized")),
], ids=["int8-mlp-calibrated", "int8-conv", "fp32-prioritized"])
def test_actor_learner_chunk_names_every_phase(env_name, net_kwargs,
                                               overrides):
    """Each phase names operations of the compiled actor-learner chunk."""
    text = _actor_learner_text(env_name, net_kwargs, **overrides)
    assert _phases_named(text) == set(common.PHASES)


@pytest.mark.parametrize("algo", ["dqn", "ddpg"])
def test_fused_iteration_names_every_phase(algo):
    """The fused iterations scope their pack, rollout, replay and update."""
    assert _phases_named(_fused_text(algo)) == set(common.PHASES)


def test_scopes_change_only_op_metadata(monkeypatch):
    """With op metadata stripped, the chunk compiles to the same program
    with and without the phase scopes."""
    args = ("airnav", dict(hidden=(32, 32)))
    kw = dict(actor_backend="int8", calib_batch=8)
    scoped = _actor_learner_text(*args, **kw)
    monkeypatch.setattr(common, "phase", _no_scope)
    bare = _actor_learner_text(*args, **kw)
    assert _phases_named(bare) == set()
    assert _program(scoped) == _program(bare)


def test_phase_rejects_unknown_names():
    with pytest.raises(ValueError, match="phase must be one of"):
        common.phase("learner")


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


@pytest.mark.parametrize("topology", ["actor-learner", "async"])
def test_train_writes_operator_spans(tmp_path, topology):
    """A traced two-chunk run carries one span per chunk dispatch, per
    record-point evaluation and per checkpoint save."""
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        loops.train("dqn", "cartpole", iterations=2, steps_per_call=1,
                    record_every=1, eval_episodes=1, topology=topology,
                    num_actors=1, algo_overrides=TINY,
                    checkpoint_dir=str(tmp_path / "ckpt"),
                    checkpoint_every=1)
    names = _host_span_names(trace_dir)
    assert {"train.chunk", "train.eval", "train.checkpoint"} <= names
