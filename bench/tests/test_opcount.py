"""``opcount`` against counts made by hand from the paper's widths."""
import json
import os

import pytest

import opcount

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    """A configuration file of the benchmark."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one observation's forward pass, GEMM by GEMM (2 M K N)
POLICY_C = [100 * 9 * 1024 * 2,            # conv0: 100 pixels, K = 3*3*1
            100 * 9216 * 1024 * 2,         # conv1: K = 3*3*1024
            100 * 9216 * 1024 * 2,         # conv2
            102400 * 2048 * 2,             # fc: 10*10*1024 -> 2048
            2048 * 3 * 2]                  # out: 3 actions
POLICY_III = [9 * 4096 * 2, 4096 * 512 * 2, 512 * 1024 * 2, 1024 * 25 * 2]


@pytest.mark.parametrize("name,hand", [("policy_c_catch", POLICY_C),
                                       ("policy_iii_airnav", POLICY_III)])
def test_forward_ops(name, hand):
    """One observation's GEMMs match the hand count."""
    gs = opcount.gemms(config(name), 1)
    assert [2 * m * k * n for m, k, n in gs] == hand
    assert opcount.gemm_ops(opcount.gemms(config(name), 64)) == 64 * sum(hand)


def test_policy_c_is_4_2_gop_per_observation():
    """Policy C does 4.2 GOP per observation."""
    assert sum(POLICY_C) == 4_196_159_488


def test_learner_flops_counts_forward_backward_and_target():
    """A learner update counts forward, backward and target forward."""
    c = config("policy_c_catch")
    fwd = 256 * sum(POLICY_C)
    first_dx = 256 * POLICY_C[0]
    assert opcount.learner_flops(c, 256) == 4 * fwd - first_dx


def test_int8_matmul_bytes_are_int8_operands_and_f32_result():
    """int8 operands once and an f32 result once."""
    assert opcount.int8_matmul_bytes(64, 102400, 2048) == (
        64 * 102400 + 102400 * 2048 + 4 * 64 * 2048)


def test_pushes_follow_the_sync_cadence():
    """Pushes land on multiples of sync_every."""
    assert opcount.pushes(0, 8, 4) == 2
    assert opcount.pushes(8, 1, 4) == 0
    assert opcount.pushes(11, 1, 4) == 1       # iteration 12
    assert opcount.pushes(9, 11, 4) == 3       # iterations 12, 16, 20


def test_work_policy_c():
    """Policy C's window work, kernel by kernel."""
    traffic = {"num_actors": 4, "n_envs": 16, "rollout_steps": 64,
               "updates_per_iter": 1, "batch_size": 256}
    w = opcount.work(config("policy_c_catch"), traffic, 1, 8, 2)
    forwards = 8 * 64 + 2             # rollout steps + divergence at pushes
    assert w["kernels"]["int8_matmul"]["ops"] == forwards * 64 * sum(POLICY_C)
    assert w["kernels"]["int8_matmul"]["calls"] == forwards * 5
    assert "fused_qmlp" not in w["kernels"]
    assert w["env_steps"] == 8 * 64 * 64
    assert w["updates"] == 8
    assert w["learner_flops"] == (
        8 * (4 * 256 * sum(POLICY_C) - 256 * POLICY_C[0])
        + 2 * 64 * sum(POLICY_C))


def test_work_policy_iii_fused_and_calibration():
    """Policy III's fused kernel and calibration passes."""
    traffic = {"num_actors": 4, "n_envs": 1024, "rollout_steps": 8,
               "updates_per_iter": 1, "batch_size": 256}
    w = opcount.work(config("policy_iii_airnav"), traffic, 1, 32, 8)
    fused = w["kernels"]["fused_qmlp"]
    assert fused["ops"] == (32 * 8 + 8) * 4096 * sum(POLICY_III)
    assert fused["calls"] == 32 * 8 + 8
    # calibration at each of the 8 pushes: per-layer int8_matmul over 256
    assert w["kernels"]["int8_matmul"]["ops"] == 8 * 256 * sum(POLICY_III)
    assert w["env_steps"] == 32 * 8 * 4096


def test_work_is_per_chip_on_a_mesh():
    """Four chips each do the one-chip cell's work."""
    traffic = {"num_actors": 16, "n_envs": 16, "rollout_steps": 64,
               "updates_per_iter": 1, "batch_size": 1024}
    one = opcount.work(config("policy_c_catch"),
                       dict(traffic, num_actors=4, batch_size=256), 1, 8, 2)
    four = opcount.work(config("policy_c_catch"), traffic, 4, 8, 2)
    assert four == one


def test_roofline_share_takes_the_larger_bound():
    """The roofline takes the larger of its two bounds."""
    ctx = {"trace": {"kernels": {"int8_matmul": 2.0}},
           "work": {"kernels": {"int8_matmul": {"ops": 393e12,
                                                "bytes": 1e9}}},
           "peaks": {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}}
    assert opcount.roofline_share(ctx, "int8_matmul") == pytest.approx(50.0)
    assert opcount.roofline_share(ctx, "fused_qmlp") is None
