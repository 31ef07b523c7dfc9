"""Bitwise-resume anchor tests (ISSUE 8 acceptance contract).

The headline fault-tolerance claim: training to step k with
checkpointing on, then resuming from the newest committed step and
training to n, produces final params, optimizer state, and every
recorded metric **bitwise identical** to the uninterrupted run to n —
for DQN and DDPG across all three topologies with the packed int8 actor
cache in the state.  Checkpoint cadence never clips chunk/round
boundaries and the save lands after each loop body's eval PRNG split,
so enabling checkpointing cannot perturb the trajectory either (also
asserted: the uninterrupted reference runs *without* a checkpoint dir).

The slow marker carries the fresh-process variant: phase 1 trains and
checkpoints in one subprocess, phase 2 resumes in a second subprocess —
nothing shared but the checkpoint directory.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.rl import loops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = dict(n_envs=2, rollout_steps=2, updates_per_iter=2,
             buffer_size=64, batch_size=8, warmup=8)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _train(algo, env, topo, *, ckpt_dir=None, iterations=6, resume=False,
           net_kwargs=None, actor_backend="int8", **kw):
    multi = topo != "fused"
    return loops.train(
        algo, env, iterations=iterations, seed=3, record_every=3,
        eval_episodes=2, actor_backend=actor_backend,
        algo_overrides=dict(SMALL),
        net_kwargs=net_kwargs or dict(hidden=(16,)),
        topology=topo, num_actors=2 if multi else 1,
        sync_every=2 if multi else 1,
        checkpoint_dir=ckpt_dir, checkpoint_every=3 if ckpt_dir else 0,
        resume=resume, **kw)


def _assert_bitwise(full, res):
    for a, b in zip(_leaves(full.state), _leaves(res.state)):
        np.testing.assert_array_equal(a, b)
    assert full.rewards == res.rewards
    assert full.action_variances == res.action_variances
    assert full.divergences == res.divergences
    assert full.actor_lags == res.actor_lags


@pytest.mark.parametrize("topo", ["fused", "actor-learner", "async"])
@pytest.mark.parametrize("algo,env", [("dqn", "catch"),
                                      ("ddpg", "pendulum")])
def test_resume_bitwise_identical(tmp_path, algo, env, topo):
    d = str(tmp_path / "ckpt")
    full = _train(algo, env, topo)                     # no checkpointing
    _train(algo, env, topo, ckpt_dir=d, iterations=3)  # killed at k=3
    res = _train(algo, env, topo, ckpt_dir=d, resume=True)
    _assert_bitwise(full, res)
    # the final-boundary save committed too, and retention kept both
    from repro.checkpoint import CheckpointManager
    assert CheckpointManager(d).steps() == [3, 6]


def test_resume_bitwise_prioritized_replay(tmp_path):
    """PER sum-trees (per-shard) ride the same contract."""
    d = str(tmp_path / "ckpt")
    kw = dict(replay="prioritized", priority_exponent=0.6)
    full = _train("dqn", "catch", "actor-learner", **kw)
    _train("dqn", "catch", "actor-learner", ckpt_dir=d, iterations=3, **kw)
    res = _train("dqn", "catch", "actor-learner", ckpt_dir=d, resume=True,
                 **kw)
    _assert_bitwise(full, res)


@pytest.mark.parametrize("topo", ["fused", "async"])
def test_resume_bitwise_seq_policy(tmp_path, topo):
    """Sequence policies ride the contract too: the int8 KV-cache actor
    state (``rl.actorq.seq_cache_zeros`` riding in the env state via
    ``attach_policy_state``) is checkpointed and restored bitwise with
    the rest of the training state."""
    d = str(tmp_path / "ckpt")
    kw = dict(net_kwargs={"transformer": dict(d_model=16, n_layers=1,
                                              d_ff=32)})
    full = _train("dqn", "catch_seq", topo, **kw)
    _train("dqn", "catch_seq", topo, ckpt_dir=d, iterations=3, **kw)
    res = _train("dqn", "catch_seq", topo, ckpt_dir=d, resume=True, **kw)
    _assert_bitwise(full, res)


@pytest.mark.parametrize("topo", ["actor-learner", "async"])
def test_resume_bitwise_int4_conv_cache(tmp_path, topo):
    """A byte-packed (W4A8) conv cache in the state is re-minted from its
    actor params on restore, and the resumed run stays bitwise on the
    uninterrupted one."""
    d = str(tmp_path / "ckpt")
    kw = dict(actor_backend="int4",
              net_kwargs=dict(conv_filters=(4,), fc_width=16))
    full = _train("dqn", "catch", topo, **kw)
    _train("dqn", "catch", topo, ckpt_dir=d, iterations=3, **kw)
    res = _train("dqn", "catch", topo, ckpt_dir=d, resume=True, **kw)
    _assert_bitwise(full, res)


def test_resume_noop_without_checkpoint(tmp_path):
    """resume=True over an empty directory starts from scratch."""
    full = _train("dqn", "catch", "fused")
    res = _train("dqn", "catch", "fused",
                 ckpt_dir=str(tmp_path / "empty"), resume=True)
    _assert_bitwise(full, res)


def test_checkpoint_knobs_validated():
    with pytest.raises(ValueError, match="needs checkpoint_dir"):
        loops.train("dqn", "catch", iterations=1, resume=True,
                    algo_overrides=dict(SMALL))
    with pytest.raises(ValueError, match="needs checkpoint_dir"):
        loops.train("dqn", "catch", iterations=1, checkpoint_every=5,
                    algo_overrides=dict(SMALL))


_PHASE_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np, jax
    from repro.rl import loops

    ckpt_dir, iterations, resume, out = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1", sys.argv[4])
    res = loops.train(
        "dqn", "catch", iterations=iterations, seed=3, record_every=3,
        eval_episodes=2, actor_backend="int8", topology="async",
        num_actors=2, sync_every=2,
        algo_overrides=dict(n_envs=2, rollout_steps=2, updates_per_iter=2,
                            buffer_size=64, batch_size=8, warmup=8),
        net_kwargs=dict(hidden=(16,)),
        checkpoint_dir=ckpt_dir or None,
        checkpoint_every=3 if ckpt_dir else 0, resume=resume)
    leaves = [np.asarray(x).tolist()
              for x in jax.tree_util.tree_leaves(res.state.params)]
    json.dump({"params": leaves, "rewards": res.rewards}, open(out, "w"))
""")


@pytest.mark.slow
def test_resume_across_processes(tmp_path):
    """Fresh process-level state: nothing survives phase 1 except the
    checkpoint directory, and phase 2 still matches the uninterrupted
    single-process reference bitwise."""
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

    def phase(ckpt_dir, iterations, resume, out):
        subprocess.run(
            [sys.executable, "-c", _PHASE_SCRIPT, ckpt_dir,
             str(iterations), "1" if resume else "0", out],
            check=True, env=env, cwd=REPO, timeout=600)

    d = str(tmp_path / "ckpt")
    phase("", 6, False, str(tmp_path / "full.json"))
    phase(d, 3, False, str(tmp_path / "phase1.json"))
    phase(d, 6, True, str(tmp_path / "resumed.json"))

    full = json.load(open(tmp_path / "full.json"))
    res = json.load(open(tmp_path / "resumed.json"))
    assert full["rewards"] == res["rewards"]
    for a, b in zip(full["params"], res["params"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
