"""The rows the check re-derives are the rows the program's learner got.

``drive_actor_learner.sampled_rows`` and the replay layout in
``gather_rows`` mirror the program's key chain and replay instead of
calling it.  Here the program's TD update is wrapped to record every batch
it receives, and for each update the rows the check gathers have to be
that batch, row for row as a multiset (the loss is a mean over the batch).
"""
import os

import jax
import numpy as np
import pytest

import check
import run
from drive_actor_learner import ActorLearnerDriver

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEQ_DATA = os.path.join(DATA, "seq")
FIELDS = ("obs", "action", "reward", "done", "next_obs")


def _rows(fields):
    """One sortable key per row of a batch given as ``{field: array}``."""
    n = len(fields["reward"])
    return sorted(b"|".join(np.ascontiguousarray(fields[f][i]).tobytes()
                            for f in FIELDS) for i in range(n))


@pytest.mark.parametrize("name,data", [("conv.tiny", DATA),
                                       ("mlp.tiny", DATA),
                                       ("conv.tiny.x4", DATA),
                                       ("seq.tiny", SEQ_DATA)])
def test_sampled_rows_are_the_learner_batches(monkeypatch, name, data):
    """Each update's re-derived rows are the batch the TD update got."""
    from repro.rl import dqn
    seen = {}
    orig = dqn.make_td_update

    def record(step, batch):
        seen.setdefault(int(step), []).append(
            {f: np.asarray(getattr(batch, f)) for f in FIELDS})

    def make(env, net, cfg):
        """The program's TD update, recording the batch it is given."""
        td_update = orig(env, net, cfg)

        def recording(state, batch, replay_size, weights=None,
                      reduce=lambda x: x):
            jax.debug.callback(record, state.opt.step, batch)
            return td_update(state, batch, replay_size, weights, reduce)
        return recording

    monkeypatch.setattr(dqn, "make_td_update", make)
    spec = run.load_cell(name, os.path.join(data, "BENCHMARK.json"), data)
    chips = spec["cell"]["chips"]
    mesh = jax.make_mesh((chips,), ("actor",),
                         devices=jax.devices()[:chips]) if chips > 1 else None
    drv = ActorLearnerDriver(spec["policy"], spec["traffic"], mesh)
    _, stash = check.first_iterations(drv, 7, spec["traffic"], run.key_of)
    jax.effects_barrier()
    assert len(seen) == len(stash["batches"])
    for u, derived in enumerate(stash["batches"]):
        got = {f: np.concatenate([b[f] for b in seen[u]]) for f in FIELDS}
        assert _rows(got) == _rows(derived), (
            f"update {u}: drive_actor_learner.sampled_rows, the benchmark's "
            f"mirror of the program's key chain and replay layout, no "
            f"longer gives the rows the program's TD update receives; the "
            f"correctness check would fail a sound program. Mend the "
            f"mirror, or read the sampled indices from the program")
