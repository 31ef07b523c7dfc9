"""The controls come out not correct, at a size a CPU test can hold.

``control.py`` takes these readings on the chip at the cells' own sizes;
here a small conv cell runs them on the CPU: the reference computed in
bfloat16 in the program's place, the program's own W4A8 actors, and the
faults planted in the reference, each fails at least one of the cell's
limits while the program passes them all.
"""
import os

import pytest

import check
import control
import run
from drive_actor_learner import ActorLearnerDriver

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def readings():
    """One seed's readings of the small conv cell, and its int4 control."""
    spec = run.load_cell("conv.tiny", os.path.join(DATA, "BENCHMARK.json"),
                         DATA)
    drv = ActorLearnerDriver(spec["policy"], spec["traffic"])
    out = control._readings(drv, spec, 11, 1)
    pol4 = spec["policy"].with_config(actor_backend="int4")
    drv4 = ActorLearnerDriver(pol4, spec["traffic"])
    _, stash = check.first_iterations(drv4, 11, spec["traffic"],
                                      run.key_of)
    w0 = check.seed_weights(pol4, run.key_of(11, 0))
    refr = check.reference_run(stash, pol4, spec["traffic"], w0)
    out["control_int4"] = check.actor_numbers(pol4.forward, w0,
                                              refr["pushed"], stash)
    return spec["limits"], out


def test_program_passes(readings):
    """The program's own numbers sit under every limit."""
    limits, out = readings
    assert check.compare(out["program"], limits)


@pytest.mark.parametrize("kind", ["control_bf16", "fault_half_batch",
                                  "fault_action", "fault_push_skipped"])
def test_control_and_faults_fail(readings, kind):
    """The bf16 control and each planted fault fail a limit."""
    limits, out = readings
    assert not check.compare(out[kind], limits), out[kind]


@pytest.mark.parametrize("number", ["action_gap", "push_action_gap"])
def test_int4_actors_read_wider_than_int8(readings, number):
    """The program's W4A8 actors read a wider action gap, before the first
    push and after it."""
    _, out = readings
    assert (out["control_int4"][number]
            > max(3 * out["program"][number], 0.0))
