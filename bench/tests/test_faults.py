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
# a cell of a kind the benchmark does not have, brought as files of its
# own: the configuration, traffic, limits and the kind (policies/seq.py)
SEQ_DATA = os.path.join(DATA, "seq")
PEAKS = {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11}


def run_tiny(name, seed=7):
    """A whole run of a small cell on the CPU devices."""
    chips = 4 if name.endswith("x4") else 1
    data = SEQ_DATA if name.startswith("seq.") else DATA
    return run.run_cell(name, seed, 0.2, False,
                        devices=jax.devices()[:chips], peaks=PEAKS,
                        bench_path=os.path.join(data, "BENCHMARK.json"),
                        data_dir=data)


# The checks of seed 7's runs, as the harness gave them before the kinds
# were files of their own.
BEFORE_KINDS = {
    "conv.tiny": {"opt_steps": 0.0, "loss_gap": 1.6471334031461675e-07,
                  "grad_gap": 9.083098451976613e-08,
                  "update_gap": 4.4890370808275296e-06, "action_gap": 0.0,
                  "push_action_gap": 0.0},
    "mlp.tiny": {"opt_steps": 0.0, "loss_gap": 0.0, "grad_gap": 0.0,
                 "update_gap": 3.7889015042469416e-07,
                 "action_gap": 0.006502251606434584, "push_action_gap": 0.0},
    "conv.tiny.x4": {"opt_steps": 0.0, "loss_gap": 2.2095207512665158e-07,
                     "grad_gap": 3.704424557282369e-08,
                     "update_gap": 2.804355444440761e-06,
                     "action_gap": 0.005202496889978647,
                     "replica_gap": 0.0, "push_action_gap": 0.0},
}


@pytest.fixture(scope="module")
def sound_run():
    """Seed 7's unbroken run of a small cell, each run once."""
    runs = {}

    def get(name):
        if name not in runs:
            runs[name] = run_tiny(name)
        return runs[name]
    return get


def failing(out):
    """The names of the numbers over their limits."""
    return {k for k, v in out["checks"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("name", ["conv.tiny", "mlp.tiny", "conv.tiny.x4",
                                  "seq.tiny"])
def test_sound_run_is_correct(sound_run, name):
    """An unbroken run is correct and ends on its checks."""
    out = sound_run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", list(BEFORE_KINDS))
def test_checks_equal_their_readings_before_kinds(sound_run, name):
    """Each number a run compares is, on the same seed, what it was before
    the kinds were files of their own."""
    out = sound_run(name)
    assert {k: v["value"] for k, v in out["checks"].items()} \
        == BEFORE_KINDS[name]


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


@pytest.mark.parametrize("name", ["conv.tiny", "seq.tiny"])
def test_push_skipped(monkeypatch, name):
    """Actors that keep their first cache past a push are caught; the
    sequence actors go on decoding on their own KV caches."""
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
    out = run_tiny(name)
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
