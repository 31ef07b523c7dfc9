"""``trace_reduce`` on a recorded TPU trace and on a hand-made one."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, end, **stats):
    """A trace event."""
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start,
              stats=list(stats.items()))


def device(n, events):
    """A device plane holding ``events`` on its XLA Ops line."""
    return NS(name=f"/device:TPU:{n}",
              lines=[NS(name="XLA Modules", events=[]),
                     NS(name="XLA Ops", events=events)])


def profile(devices):
    """A profile with the host spans and ``devices``."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.dispatch", 0, 100), ev("bench.wait", 100, 1000)])])
    return NS(planes=[host] + devices)


def test_busy_union_ops_kernels_and_collectives():
    """Busy union, per-op sums, kernels, collectives and gaps."""
    d0 = device(0, [
        ev("fusion.1", 100, 300),
        ev("fusion.2", 200, 400),                 # overlaps fusion.1
        ev("%int8_matmul.7 = f32[8,8] custom-call(s8[8,8] %x)", 500, 700),
        ev("%reshape.2 = f32[8,8] reshape(f32[8,8] %int8_matmul.7)", 700,
           750),
        ev("%all-reduce.3 = f32[8] all-reduce(f32[8] %g)", 800, 900),
        ev("%while.1 = (s32[]{:T(128)}) while((s32[]) %t)", 0, 1000),
        ev("fusion.9", 1500, 1600),               # outside the window
    ])
    red = trace_reduce.reduce_profile(profile([d0]), n_devices=1)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((300 + 250 + 100) * 1e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(200e-9)
    assert not any(k.startswith("%while") for k in red["ops"])
    assert red["kernels"] == {"int8_matmul": pytest.approx(200e-9)}
    assert red["collective_s"] == pytest.approx(100e-9)
    idle = sum(red["idle_by_span"].values())
    assert idle == pytest.approx(350e-9)
    assert red["idle_by_span"]["bench.dispatch"] == pytest.approx(100e-9)


def test_averages_over_chips():
    """Numbers are averages over the chips used."""
    d0 = device(0, [ev("fusion.1", 0, 1000)])
    d1 = device(1, [ev("fusion.1", 0, 500)])
    red = trace_reduce.reduce_profile(profile([d1, d0]), n_devices=2)
    assert red["busy_s"] == pytest.approx(750e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(750e-9)


def test_breakdown_keeps_ten_of_each():
    """The breakdown keeps ten operations and ten gaps."""
    d0 = device(0, [ev(f"fusion.{i}", 50 * i, 50 * i + 10)
                    for i in range(20)])
    out = trace_reduce.breakdown(
        trace_reduce.reduce_profile(profile([d0]), n_devices=1))
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10


RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_*", "**",
                                         "*.xplane.pb"), recursive=True))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_tpu_trace(path):
    """A trace recorded on a v5e reduces to sane numbers."""
    chips = 4 if "4chip" in path else 1
    red = trace_reduce.reduce_profile(trace_reduce.load(path), chips)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernels"]["int8_matmul"] > 0
    assert red["kernels"]["fused_qmlp"] > 0
    assert sum(red["kernels"].values()) < red["busy_s"]
    if chips > 1:
        assert red["collective_s"] > 0
    else:
        assert red["collective_s"] == 0
