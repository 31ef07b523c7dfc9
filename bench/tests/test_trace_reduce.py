"""``trace_reduce`` on a recorded TPU trace and on a hand-made one, and
the per-layer readers on the recorded traces."""
import glob
import json
import os
from types import SimpleNamespace as NS

import pytest

import kinds
import opcount
import run
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the int8 kernels the recorded cells ran
KERNELS_RUN = ("int8_matmul", "fused_qmlp")


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
    red = trace_reduce.reduce_profile(profile([d0]), 1, ("int8_matmul",))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((300 + 250 + 100) * 1e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(200e-9)
    assert not any(k.startswith("%while") for k in red["ops"])
    assert red["kernels"] == {"int8_matmul": pytest.approx(200e-9)}
    assert red["collective_s"] == pytest.approx(100e-9)
    idle = sum(red["idle_by_span"].values())
    assert idle == pytest.approx(350e-9)
    assert red["idle_by_span"]["bench.dispatch"] == pytest.approx(100e-9)


def test_counts_the_kernels_named_and_no_others():
    """Only the kernels the work names are counted, a new one among them
    by its name alone."""
    d0 = device(0, [
        ev("%int8_matmul.7 = f32[8,8] custom-call(s8[8,8] %x)", 100, 200),
        ev("%fused_qmlp.2 = f32[8,3] custom-call(f32[8,9] %x)", 200, 250),
        ev("%int8_cache_attention.1 = f32[8,16] custom-call(s8[8,4,16] %k)",
           300, 330),
        ev("%fusion.int8_matmul = f32[8] fusion(f32[8] %p)", 400, 500),
    ])
    red = trace_reduce.reduce_profile(
        profile([d0]), 1, ("int8_matmul", "int8_cache_attention"))
    assert red["kernels"] == {"int8_matmul": pytest.approx(100e-9),
                              "int8_cache_attention": pytest.approx(30e-9)}


def test_averages_over_chips():
    """Numbers are averages over the chips used."""
    d0 = device(0, [ev("fusion.1", 0, 1000)])
    d1 = device(1, [ev("fusion.1", 0, 500)])
    red = trace_reduce.reduce_profile(profile([d1, d0]), 2, KERNELS_RUN)
    assert red["busy_s"] == pytest.approx(750e-9)
    assert red["ops"]["fusion.1"] == pytest.approx(750e-9)


def test_breakdown_keeps_ten_of_each():
    """The breakdown keeps ten operations and ten gaps."""
    d0 = device(0, [ev(f"fusion.{i}", 50 * i, 50 * i + 10)
                    for i in range(20)])
    out = trace_reduce.breakdown(
        trace_reduce.reduce_profile(profile([d0]), 1, KERNELS_RUN))
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10


RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_*", "**",
                                         "*.xplane.pb"), recursive=True))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_tpu_trace(path):
    """A trace recorded on a v5e reduces to sane numbers."""
    chips = 4 if "4chip" in path else 1
    red = trace_reduce.reduce_profile(trace_reduce.load(path), chips,
                                      KERNELS_RUN)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernels"]["int8_matmul"] > 0
    assert red["kernels"]["fused_qmlp"] > 0
    assert sum(red["kernels"].values()) < red["busy_s"]
    if chips > 1:
        assert red["collective_s"] > 0
    else:
        assert red["collective_s"] == 0


PEAKS_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
             "hbm_bytes_per_s": 819e9}
READERS = ("device_idle_share", "step_mfu", "roofline.int8_matmul",
           "roofline.fused_qmlp", "kernel_share.int8", "dispatch_ms")


def _work(name, mix, n_iters, n_pushes):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{mix}.json")) as f:
        traffic = json.load(f)
    return opcount.work(kinds.bind(config), traffic, 1, n_iters, n_pushes)


# What each reader gave before the kinds were files of their own, on the
# recorded traces with a production cell's work in the window.  The traces
# are of small cells, so most of these are no reading a chip would give
# (shares far above 100%): they pin the arithmetic, reader by reader.
# Policy C's work names int8_matmul only, so its kernel_share.int8 counts
# no fused_qmlp, which its own traces hold none of; on these traces, which
# hold both, it is left out.
BEFORE_KINDS = {
    ("trace_tpu_1chip", "III"): {
        "device_idle_share": 99.4492587109746,
        "dispatch_ms": 0.2866600000000011,
        "kernel_share.int8": 25.90868972157586,
        "roofline.fused_qmlp": 6810183.366201905,
        "roofline.int8_matmul": 51890.84804827777,
        "step_mfu": 5861.446677052893},
    ("trace_tpu_4chip", "III"): {
        "device_idle_share": 97.13748072330846,
        "dispatch_ms": 2.057233333333334,
        "kernel_share.int8": 0.3013587727021743,
        "roofline.fused_qmlp": 27225041.798065215,
        "roofline.int8_matmul": 207424.55380368422,
        "step_mfu": 1416.5603075125532},
    ("trace_tpu_1chip", "C"): {
        "device_idle_share": 99.4492587109746,
        "dispatch_ms": 0.2866600000000011,
        "roofline.fused_qmlp": None,
        "roofline.int8_matmul": 96967159.44896464,
        "step_mfu": 96303.31551853461},
    ("trace_tpu_4chip", "C"): {
        "device_idle_share": 97.13748072330846,
        "dispatch_ms": 2.057233333333334,
        "roofline.fused_qmlp": None,
        "roofline.int8_matmul": 387609193.8909784,
        "step_mfu": 23274.024615712253},
}
WORK = {"C": ("policy_c_catch", "rollout_heavy_64env", 33, 8),
        "III": ("policy_iii_airnav", "rollout_heavy_4096env", 256, 64)}


@pytest.mark.parametrize("case", list(BEFORE_KINDS),
                         ids=lambda c: f"{c[0]}-{c[1]}")
def test_readers_equal_their_readings_before_kinds(case):
    """Every per-layer reader reads on the recorded traces what it read
    before the kinds were files of their own."""
    trace, cell = case
    chips = 4 if "4chip" in trace else 1
    prof = trace_reduce.load(os.path.join(DATA, trace))
    work = _work(*WORK[cell])
    ctx = {"trace": trace_reduce.reduce_profile(prof, chips,
                                                tuple(work["kernels"])),
           "spans": [(n, s * 1e-9, e * 1e-9)
                     for n, s, e in trace_reduce.host_spans(prof)],
           "work": work, "chips": chips, "peaks": PEAKS_V5E}
    got = {m: run.load_reader(m)(ctx) for m in READERS}
    want = BEFORE_KINDS[case]
    assert {m: got[m] for m in want} == want
