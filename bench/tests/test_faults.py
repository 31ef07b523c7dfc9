"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a small cell on the CPU (the harness's look
for a chip is skipped: devices and peaks are passed in) with one fault
planted in the program, and sees ``correct`` false and the number that
catches it over its limit.  A sound run of the same cell is correct.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11}


def run_tiny(name, seed=7):
    """A whole run of a small cell on the CPU devices."""
    chips = 4 if name.endswith("x4") else 1
    return run.run_cell(name, seed, 0.2, False,
                        devices=jax.devices()[:chips], peaks=PEAKS,
                        bench_path=os.path.join(DATA, "BENCHMARK.json"),
                        data_dir=DATA)


def failing(out):
    """The names of the numbers over their limits."""
    return {k for k, v in out["checks"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("name", ["conv.tiny", "mlp.tiny", "conv.tiny.x4"])
def test_sound_run_is_correct(name):
    """An unbroken run is correct and ends on its checks."""
    out = run_tiny(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def test_state_returned_unchanged(monkeypatch):
    """A step that returns its state unchanged is caught."""
    from repro.rl import loops
    orig = loops.make_scan_iteration

    def broken(iteration, n):
        """The chunk of an iteration that drops its new state."""
        def same_state(state, env_state, obs, key):
            _, env_state, obs, metrics = iteration(state, env_state, obs,
                                                   key)
            return state, env_state, obs, metrics
        return orig(same_state, n)

    monkeypatch.setattr(loops, "make_scan_iteration", broken)
    out = run_tiny("conv.tiny")
    assert not out["correct"]
    assert "opt_steps" in failing(out)


def test_push_skipped(monkeypatch):
    """Actors that keep their first cache past a push are caught."""
    from repro.rl import loops
    orig = loops.make_scan_iteration

    def broken(iteration, n):
        """The chunk of an iteration whose parameter push never lands."""
        def no_push(state, env_state, obs, key):
            new, env_state, obs, metrics = iteration(state, env_state, obs,
                                                     key)
            new = new._replace(actor_params=state.actor_params,
                               actor_cache=state.actor_cache)
            return new, env_state, obs, metrics
        return orig(no_push, n)

    monkeypatch.setattr(loops, "make_scan_iteration", broken)
    out = run_tiny("conv.tiny")
    assert not out["correct"]
    assert "push_action_gap" in failing(out)


def _patch_td_update(monkeypatch, wrap):
    from repro.rl import dqn
    orig = dqn.make_td_update

    def make(env, net, cfg):
        """The program's TD update, wrapped."""
        return wrap(orig(env, net, cfg))

    monkeypatch.setattr(dqn, "make_td_update", make)


def test_half_batch_left_out(monkeypatch):
    """A learner that averages over half its batch is caught."""
    def wrap(td_update):
        def half(state, batch, replay_size, weights=None,
                 reduce=lambda x: x):
            n = batch.reward.shape[0] // 2
            batch = jax.tree_util.tree_map(lambda x: x[:n], batch)
            return td_update(state, batch, replay_size, weights, reduce)
        return half

    _patch_td_update(monkeypatch, wrap)
    out = run_tiny("conv.tiny")
    assert not out["correct"]
    assert "loss_gap" in failing(out)


def test_exchange_between_chips_left_out(monkeypatch):
    """Learners that skip the gradient pmean are caught."""
    def wrap(td_update):
        def local(state, batch, replay_size, weights=None,
                  reduce=lambda x: x):
            return td_update(state, batch, replay_size, weights)
        return local

    _patch_td_update(monkeypatch, wrap)
    out = run_tiny("conv.tiny.x4")
    assert not out["correct"]
    assert "replica_gap" in failing(out)


def test_action_altered_where_produced(monkeypatch):
    """Actions altered in the actor's head are caught."""
    from repro.rl import actorq
    orig = actorq.quantized_apply

    def shifted(qparams, x, *, backend="auto"):
        """Q-values rolled by one action."""
        return jnp.roll(orig(qparams, x, backend=backend), 1, axis=-1)

    monkeypatch.setattr(actorq, "quantized_apply", shifted)
    out = run_tiny("conv.tiny")
    assert not out["correct"]
    assert "action_gap" in failing(out)
