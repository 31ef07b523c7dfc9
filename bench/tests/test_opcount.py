"""``opcount`` and the kinds' counts against counts made by hand from
the paper's widths, and against the counts the harness gave before the
kinds were files of their own."""
import json
import os

import pytest

import kinds
import opcount

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ_DATA = os.path.join(BENCH, "tests", "data", "seq")


def config(name, data_dir=BENCH):
    """A configuration file of the benchmark."""
    with open(os.path.join(data_dir, "configs", f"{name}.json")) as f:
        return json.load(f)


def policy(name, data_dir=BENCH):
    """A configuration bound to its kind."""
    return kinds.bind(config(name, data_dir), data_dir)


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
    p = policy(name)
    gs = p.kind.gemms(p.config, 1)
    assert [2 * m * k * n for m, k, n in gs] == hand
    assert opcount.gemm_ops(p.kind.gemms(p.config, 64)) == 64 * sum(hand)


def test_policy_c_is_4_2_gop_per_observation():
    """Policy C does 4.2 GOP per observation."""
    assert sum(POLICY_C) == 4_196_159_488


def test_learner_flops_counts_forward_backward_and_target():
    """A learner update counts forward, backward and target forward."""
    p = policy("policy_c_catch")
    fwd = 256 * sum(POLICY_C)
    first_dx = 256 * POLICY_C[0]
    assert p.kind.learner_flops(p.config, 256) == 4 * fwd - first_dx


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
    w = opcount.work(policy("policy_c_catch"), traffic, 1, 8, 2)
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
    w = opcount.work(policy("policy_iii_airnav"), traffic, 1, 32, 8)
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
    one = opcount.work(policy("policy_c_catch"),
                       dict(traffic, num_actors=4, batch_size=256), 1, 8, 2)
    four = opcount.work(policy("policy_c_catch"), traffic, 4, 8, 2)
    assert four == one


def test_roofline_share_takes_the_larger_bound():
    """The roofline takes the larger of its two bounds."""
    ctx = {"trace": {"kernels": {"int8_matmul": 2.0}},
           "work": {"kernels": {"int8_matmul": {"ops": 393e12,
                                                "bytes": 1e9}}},
           "peaks": {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}}
    assert opcount.roofline_share(ctx, "int8_matmul") == pytest.approx(50.0)
    assert opcount.roofline_share(ctx, "fused_qmlp") is None


def traffic(name):
    """A traffic file of the benchmark."""
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ``opcount.work`` as the harness computed it while it counted conv and MLP
# policies itself (one chip's share; Policy C on four chips with 16 actors
# and a batch of 1,024)
BEFORE_KINDS = {
    ("policy_c_catch", "rollout_heavy_64env", 1, 33, 8): {
        "actor_int8_ops": 569334919331840.0, "env_steps": 135168,
        "kernels": {"int8_matmul": {"bytes": 916858142720.0, "calls": 10600,
                                    "ops": 569334919331840.0}},
        "learner_flops": 143929483722752.0, "updates": 33},
    ("policy_iii_airnav", "rollout_heavy_4096env", 1, 256, 64): {
        "actor_int8_ops": 46523521957888.0, "env_steps": 8388608,
        "kernels": {"fused_qmlp": {"bytes": 6611337216.0, "calls": 2112,
                                   "ops": 46435575791616.0},
                    "int8_matmul": {"bytes": 634929152.0, "calls": 256,
                                    "ops": 87946166272.0}},
        "learner_flops": 2809445482496.0, "updates": 256},
    ("policy_c_catch", "rollout_heavy_64env", 4, 9, 2): {
        "actor_int8_ops": 155224331780096.0, "env_steps": 36864,
        "kernels": {"int8_matmul": {"bytes": 249973587968.0, "calls": 2890,
                                    "ops": 155224331780096.0}},
        "learner_flops": 39204667523072.0, "updates": 9},
}


@pytest.mark.parametrize("case", list(BEFORE_KINDS),
                         ids=lambda c: f"{c[0]}-x{c[2]}")
def test_work_equals_the_count_before_kinds(case):
    """The production cells' work, now summed from their kind files, is
    what the harness counted before, number for number."""
    name, mix, chips, n_iters, n_pushes = case
    t = traffic(mix)
    if chips > 1:
        t = dict(t, num_actors=4 * t["num_actors"],
                 batch_size=4 * t["batch_size"])
    w = opcount.work(policy(name), t, chips, n_iters, n_pushes)
    assert w == BEFORE_KINDS[case]
    assert list(w["kernels"]) == list(BEFORE_KINDS[case]["kernels"])


def test_seq_kind_counts():
    """The sequence kind's counts against a hand count: a decode step of
    one token per environment, a windowed push and learner."""
    p = policy("seq_tiny", SEQ_DATA)
    s, f, d, ff, layers, acts = 4, 27, 16, 32, 2, 3
    per_token = 2 * (f * d + layers * (4 * d * d + 2 * d * ff))
    head = 2 * d * acts                          # the newest row only
    attn = layers * 4 * s * d                    # per query
    k = p.kind.rollout_kernels(p.config, 8)
    assert k["int8_matmul"][0] == 8 * (per_token + head)
    assert k["int8_matmul"][2] == 2 + 6 * layers
    assert k["int8_cache_attention"][0] == 8 * attn
    assert k["int8_cache_attention"][2] == layers
    # per query: f32 query and output, the window's int8 keys and values
    # and their f32 scales
    assert k["int8_cache_attention"][1] == layers * 8 * (
        4 * d + 4 * d + 2 * s * d + 2 * 4 * s)
    push = p.kind.push_kernels(p.config, 8)
    assert push["int8_matmul"][0] == 8 * (s * per_token + head)
    assert "int8_cache_attention" not in push
    assert p.kind.push_flops(p.config, 8) == (
        8 * (s * per_token + head) + 2 * 8 * s * attn)
    fwd = 16 * (s * per_token + head) + 16 * s * attn
    assert p.kind.learner_flops(p.config, 16) == 4 * fwd - 2 * 16 * s * f * d
