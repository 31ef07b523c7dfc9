"""``xplane.py`` and ``phases.py`` on the recorded TPU traces and by hand."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

import phases
import trace_reduce
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_*", "**",
                                         "*.xplane.pb"), recursive=True))
SCOPED = os.path.join(DATA, "phases_tpu_1chip", "tiny.xplane.pb")
# the int8 kernels the recorded cells ran
KERNELS_RUN = ("int8_matmul", "fused_qmlp")


def chips_of(path):
    return 4 if "4chip" in path else 1


@pytest.mark.parametrize("op_name,phase", [
    ("jit(chunk)/while/body/closed_call/learner_update/replay_sample/"
     "jit(_randint)/xor", "replay_sample"),
    ("jit(chunk)/while/body/closed_call/learner_update/transpose(jvp())/mul",
     "learner_update"),
    ("jit(chunk)/while/body/learner_update/jvp(replay_sample)/jit(_take)/"
     "gather", "replay_sample"),
    ("jit(chunk)/while/body/actor_forward/jit(fused_qmlp)/pallas_call",
     "actor_forward"),
    ("jit(g)/param_push/while/body/closed_call/env_step/select_n",
     "env_step"),
    ("jit(chunk)/while/body/closed_call/replay_insert/scatter",
     "replay_insert"),
    ("jit(chunk)/while/body/closed_call/param_push/cond/branch_1_fun/"
     "jit(int8_matmul)/pallas_call", "param_push"),
    ("jit(step)/jit(fused_qmlp)/div", "other"),
    ("", "other"),
    ("jit(chunk)/while/body/my_learner_update_x/add", "other"),
])
def test_innermost_phase_owns_the_op(op_name, phase):
    """The innermost phase named, bare or inside transformations."""
    assert phases.phase_of(op_name) == phase


def test_phase_names_are_the_programs():
    """``phases.PHASES`` lists the program's phases, in its order."""
    import drive_actor_learner  # noqa: F401  (puts the program on the path)
    from repro.rl import common
    assert phases.PHASES == common.PHASES


@pytest.mark.parametrize("path", RECORDED + [SCOPED])
def test_reader_matches_profile_data(path):
    """``trace_reduce`` reads the same numbers from ``xplane.read`` as from
    ``ProfileData``."""
    n = chips_of(path)
    assert (trace_reduce.reduce_profile(xplane.read(path), n, KERNELS_RUN)
            == trace_reduce.reduce_profile(trace_reduce.load(path), n,
                                           KERNELS_RUN))


@pytest.mark.parametrize("path", RECORDED + [SCOPED])
def test_reader_finds_tf_op_of_kernels(path):
    """The int8 kernels' device events carry their HLO ``op_name``."""
    found = set()
    for p in trace_reduce.device_planes(xplane.read(path), chips_of(path)):
        for line in p.lines:
            for ev in line.events:
                name, opcode = trace_reduce.parse_op(ev.name)
                k = trace_reduce.kernel_of(name, opcode, KERNELS_RUN)
                if k is not None:
                    assert f"jit({k})" in xplane.tf_op(ev)
                    found.add(k)
    assert found


@pytest.mark.parametrize("path", RECORDED + [SCOPED])
def test_phases_sum_to_busy(path):
    """Every instant of busy time counts once, in one phase."""
    n = chips_of(path)
    red = phases.reduce_phases(xplane.read(path), n)
    busy = trace_reduce.reduce_profile(trace_reduce.load(path), n,
                                       KERNELS_RUN)["busy_s"]
    assert sum(red["phases"].values()) == pytest.approx(busy, rel=1e-3)
    assert red["busy_s"] == pytest.approx(busy, rel=1e-3)


@pytest.mark.parametrize("path", RECORDED)
def test_unscoped_program_reads_no_phase(path):
    """A program without the scopes puts everything under ``other`` and
    gives no per-iteration phase times."""
    red = phases.reduce_phases(xplane.read(path), chips_of(path))
    assert not red["named"]
    assert set(red["phases"]) == set(phases.PHASES) | {"other"}
    assert phases.phase_ms(red, 4) == {}


def test_scoped_trace_has_every_phase():
    """The actor-learner chunk recorded on a v5e spends device time in each
    of its six phases, and its int8 kernel runs in ``actor_forward``."""
    red = phases.reduce_phases(xplane.read(SCOPED), 1)
    assert red["named"]
    for p in phases.PHASES:
        assert red["phases"][p] > 0, p
    kernels = [label for label, _ in red["ops"]["actor_forward"]
               if "fused_qmlp" in label]
    assert kernels
    per_iter = phases.phase_ms(red, 4)
    assert sum(per_iter.values()) == pytest.approx(
        1e3 * red["busy_s"] / 4)


def ev(name, start, end, tf_op=""):
    """A device event whose metadata carries ``tf_op``."""
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start,
              stats=[], metadata_stats=[("tf_op", tf_op)] if tf_op else [])


def space(events, host=()):
    """One chip's operations and host events inside a 0-1000 ns window."""
    host_line = NS(name="python", events=[
        ev("bench.dispatch", 0, 100), ev("bench.wait", 100, 1000),
        *host])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops",
                                             events=events)])
    return NS(planes=[NS(name="/host:CPU", lines=[host_line]), dev])


def test_overlap_counts_once_for_the_earlier_op():
    """An op overlapping an earlier one gets only its own remainder; the
    control flow around them counts nothing."""
    sp = space([
        ev("%while.1 = () while(s32[] %t)", 0, 1000, "jit(c)/while"),
        ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 300,
           "jit(c)/while/body/env_step/add"),
        ev("%fusion.2 = f32[8] fusion(f32[8] %p)", 200, 400,
           "jit(c)/while/body/learner_update/mul"),
        ev("%copy.1 = f32[8] copy(f32[8] %p)", 500, 600),
    ])
    red = phases.reduce_phases(sp, 1)
    assert red["phases"]["env_step"] == pytest.approx(200e-9)
    assert red["phases"]["learner_update"] == pytest.approx(100e-9)
    assert red["phases"]["other"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(
        trace_reduce.reduce_profile(sp, 1, KERNELS_RUN)["busy_s"])
    assert phases.phase_ms(red, 2)["env_step"] == pytest.approx(1e-4)


def test_op_without_op_name_takes_the_phase_its_neighbours_share():
    """An operation XLA made without an ``op_name`` belongs to the phase
    of the named operations on both sides of it, else to ``other``."""
    sp = space([
        ev("%copy.1 = f32[8] copy(f32[8] %p)", 50, 100),
        ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 200,
           "jit(c)/while/body/replay_insert/scatter"),
        ev("%fusion.2 = f32[9,64] fusion(f32[9,64] %p)", 200, 500),
        ev("%copy-done.1 = f32[8] copy-done(f32[8] %c)", 500, 520),
        ev("%fusion.3 = f32[8] fusion(f32[8] %p)", 520, 600,
           "jit(c)/while/body/replay_insert/reshape"),
        ev("%fusion.4 = f32[8] fusion(f32[8] %p)", 600, 700),
        ev("%fusion.5 = f32[8] fusion(f32[8] %p)", 700, 800,
           "jit(c)/while/body/learner_update/replay_sample/gather"),
        ev("%fusion.6 = f32[8] fusion(f32[8] %p)", 800, 900),
    ])
    red = phases.reduce_phases(sp, 1)
    assert red["phases"]["replay_insert"] == pytest.approx(500e-9)
    assert red["phases"]["replay_sample"] == pytest.approx(100e-9)
    assert red["phases"]["other"] == pytest.approx(250e-9)
    assert red["unnamed_s"] == pytest.approx(570e-9)
    assert red["inferred_s"] == pytest.approx(320e-9)


def test_idle_gaps_name_overlapping_host_events():
    """Each idle gap lists the host events in it, longest overlap first."""
    sp = space([ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 300),
                ev("%fusion.2 = f32[8] fusion(f32[8] %p)", 800, 900)],
               host=[ev("PythonRefManager::CollectGarbage", 350, 450),
                     ev("Wait for usage holds", 300, 800)])
    gaps = phases.idle_gap_events(sp, 1, top=2)
    assert [g["gap_s"] for g in gaps] == pytest.approx([500e-9, 100e-9])
    names = [name for name, _ in gaps[0]["events"]]
    assert set(names[:2]) == {"Wait for usage holds", "bench.wait"}
    assert "PythonRefManager::CollectGarbage" in names
    assert "Wait for usage holds" in phases.format_gaps(gaps)


def test_trace_cell_reads_phases_and_keeps_the_program(monkeypatch, tmp_path,
                                                       capsys):
    """``trace_cell.py`` on a tiny cell, on the CPU: the harness's look for
    a chip and its compile cache in the checkout are skipped, and the CPU
    trace, which holds no device plane, is read as the recorded scoped
    trace.  Its line holds the phases, the
    traced rate and the stripped program's hash; the harness's reduction
    is restored after it."""
    import json

    import jax

    import run
    import trace_cell
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(run, "find_devices", lambda chips: (
        jax.devices()[:chips],
        {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11}))
    read, load = xplane.read, trace_reduce.load
    monkeypatch.setattr(xplane, "read", lambda path: read(SCOPED))
    monkeypatch.setattr(trace_reduce, "load", lambda path: load(SCOPED))
    reduce_dir = trace_reduce.reduce_dir
    hlo = tmp_path / "chunk.hlo.txt"
    assert trace_cell.main([
        "--workload", "mlp.tiny", "--seed", "5", "--seconds", "0",
        "--bench", os.path.join(DATA, "BENCHMARK.json"), "--data", DATA,
        "--hlo", str(hlo)]) == 0
    assert trace_reduce.reduce_dir is reduce_dir
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    assert set(out["phase_ms"]) == set(phases.PHASES) | {"other"}
    assert out["traced_env_steps_per_s"] > 0
    text = hlo.read_text()
    assert "metadata=" not in text and "FileNames" not in text
    assert len(out["hlo_sha256"]) == 64


@pytest.mark.parametrize("path", RECORDED)
def test_trim_keeps_what_the_reductions_read(path, tmp_path):
    """A trimmed copy reduces to the same busy time, window, kernels, idle
    gaps and phases."""
    n = chips_of(path)
    out = str(tmp_path / "trimmed.xplane.pb")
    xplane.trim(path, out)
    assert os.path.getsize(out) < os.path.getsize(path)
    full = trace_reduce.reduce_profile(trace_reduce.load(path), n,
                                       KERNELS_RUN)
    cut = trace_reduce.reduce_profile(trace_reduce.load(out), n,
                                      KERNELS_RUN)
    for k in ("busy_s", "window_s", "kernels", "collective_s",
              "idle_by_span"):
        assert cut[k] == full[k], k
    assert (phases.reduce_phases(xplane.read(out), n)["phases"]
            == phases.reduce_phases(xplane.read(path), n)["phases"])
