"""Fused quantized-MLP actor kernel + W4A8 packed weights (ISSUE 5).

Acceptance contracts:
* interpret-vs-ref parity of the single-pass kernel across
  bits {4, 8} x MLP depth {1, 2, 3} x head (logits / q / mu),
* the *bitwise anchor*: with static activation scales calibrated from the
  very batch being evaluated, the fused path reproduces the per-layer
  dynamic ``quantized_mlp_apply`` exactly (eager; under jit only XLA's
  FMA fusion may differ, bounded by a tight allclose),
* ``actor_backend="int4"`` halves the packed actor-cache codes and trains/
  deploys end to end through every topology.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affine, ptq
from repro.core.fake_quant import NullQATContext
from repro.core.qconfig import QuantConfig
from repro.rl import actorq, loops
from repro.rl.networks import make_network

SMALL_DQN = dict(n_envs=4, rollout_steps=4, updates_per_iter=2,
                 buffer_size=512, batch_size=16, warmup=8)


# ---------------------------------------------------------------------------
# int4 byte packing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 7, 16, 33])
def test_pack_unpack_int4_roundtrip(k):
    codes = jax.random.randint(jax.random.PRNGKey(k), (k, 6), -8, 8
                               ).astype(jnp.int8)
    packed = affine.pack_int4(codes)
    assert packed.shape == ((k + 1) // 2, 6) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(affine.unpack_int4(packed, k)),
                                  np.asarray(codes))


def test_pack_int4_holds_k_halves_in_nibbles():
    """Byte row i = code row i (low nibble) | code row ceil(K/2)+i (high
    nibble): each nibble plane is a contiguous K half, and an odd K pads
    the last high nibble with a zero code."""
    k = 5
    codes = jax.random.randint(jax.random.PRNGKey(0), (k, 3), -8, 8
                               ).astype(jnp.int8)
    lo, hi = affine.int4_halves(affine.pack_int4(codes))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(codes[:3]))
    np.testing.assert_array_equal(np.asarray(hi[:2]), np.asarray(codes[3:]))
    np.testing.assert_array_equal(np.asarray(hi[2]), 0)


def test_quantize_with_params_matches_dynamic():
    """Static requant with params derived from the same tensor is the
    dynamic quantizer bit for bit — the fused kernel's core contract."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 33)) * 2.5
    q_dyn, p_dyn = affine.quantize_to_int(x, 8)
    p_cal = affine.calibration_params(x, 8)
    np.testing.assert_array_equal(np.asarray(p_dyn.delta),
                                  np.asarray(p_cal.delta))
    np.testing.assert_array_equal(np.asarray(p_dyn.zero_point),
                                  np.asarray(p_cal.zero_point))
    np.testing.assert_array_equal(
        np.asarray(q_dyn), np.asarray(affine.quantize_with_params(x, p_cal)))


# ---------------------------------------------------------------------------
# interpret-vs-ref parity matrix
# ---------------------------------------------------------------------------

_HEAD_OUT = {"logits": 4, "q": 3, "mu": 2}   # a2c/ppo (+value), dqn, ddpg


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("head", sorted(_HEAD_OUT))
def test_fused_kernel_interpret_matches_ref(bits, depth, head):
    out_dim = _HEAD_OUT[head]
    net = make_network((5,), out_dim, hidden=(24,) * depth)
    params = net.init(jax.random.PRNGKey(bits * 10 + depth))
    obs = jax.random.normal(jax.random.PRNGKey(depth), (9, 5)) * 2.0
    cache = actorq.calibrate_actor_cache(
        actorq.pack_actor_params(params, bits=bits), obs, backend="ref")
    assert actorq.ACT_QUANT in cache
    got_ref = actorq.quantized_apply(cache, obs, backend="ref")
    got_int = actorq.quantized_apply(cache, obs, backend="interpret")
    assert got_ref.shape == (9, out_dim)
    np.testing.assert_allclose(np.asarray(got_int), np.asarray(got_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# static-requant bitwise anchor vs the per-layer dynamic path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8])
def test_fused_static_anchor_matches_per_layer_dynamic(bits):
    """Calibrated on the batch it then evaluates, the fused single-pass
    kernel IS the per-layer dynamic path: identical affine params at every
    layer, identical integer codes, identical float epilogue order —
    bitwise equal eagerly; under jit only FMA re-association remains."""
    net = make_network((4,), 3, hidden=(32, 16, 8))
    params = net.init(jax.random.PRNGKey(1))
    obs = jax.random.normal(jax.random.PRNGKey(2), (50, 4)) * 2.0
    qp = actorq.pack_actor_params(params, bits=bits)
    with jax.disable_jit():
        cache = actorq.calibrate_actor_cache(qp, obs, backend="ref")
        fused = actorq.quantized_apply(cache, obs, backend="ref")
        per_layer = actorq.quantized_apply(qp, obs, backend="ref")
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(per_layer))
    cache = actorq.calibrate_actor_cache(qp, obs, backend="ref")
    fused_jit = actorq.quantized_apply(cache, obs, backend="ref")
    per_jit = actorq.quantized_apply(qp, obs, backend="ref")
    np.testing.assert_allclose(np.asarray(fused_jit), np.asarray(per_jit),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(fused_jit, -1)),
                                  np.asarray(jnp.argmax(per_jit, -1)))


def test_calibrated_cache_shifts_with_distribution():
    """Static scales are a property of the calibration batch: a cache
    calibrated elsewhere differs from dynamic on out-of-range data (the
    documented staleness of the static-requant contract)."""
    net = make_network((4,), 3, hidden=(16,))
    params = net.init(jax.random.PRNGKey(3))
    calib = jax.random.normal(jax.random.PRNGKey(4), (32, 4)) * 0.1
    wild = jax.random.normal(jax.random.PRNGKey(5), (32, 4)) * 10.0
    cache = actorq.calibrate_actor_cache(
        actorq.pack_actor_params(params), calib, backend="ref")
    fused = actorq.quantized_apply(cache, wild, backend="ref")
    dyn = actorq.quantized_apply(actorq.pack_actor_params(params), wild,
                                 backend="ref")
    assert np.isfinite(np.asarray(fused)).all()
    assert not np.array_equal(np.asarray(fused), np.asarray(dyn))


def test_calibrate_is_noop_for_conv_caches():
    net = make_network((6, 6, 2), 3, conv_filters=(4,), fc_width=16)
    qp = actorq.pack_actor_params(net.init(jax.random.PRNGKey(6)))
    obs = jax.random.normal(jax.random.PRNGKey(7), (3, 6, 6, 2))
    assert actorq.ACT_QUANT not in actorq.calibrate_actor_cache(qp, obs)


# ---------------------------------------------------------------------------
# W4A8: accuracy + footprint
# ---------------------------------------------------------------------------

def test_int4_mlp_close_to_fake_quant_4bit():
    net = make_network((4,), 2, hidden=(32, 32))
    params = net.init(jax.random.PRNGKey(8))
    obs = jax.random.normal(jax.random.PRNGKey(9), (32, 4)) * 2.0
    sim = net.apply(NullQATContext(),
                    ptq.ptq_simulate(params, QuantConfig.ptq_int(4)), obs)
    got = actorq.quantized_apply(actorq.pack_actor_params(params, bits=4),
                                 obs, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(sim), atol=1e-2)


def test_int4_conv_close_to_fake_quant_4bit():
    net = make_network((6, 6, 2), 3, conv_filters=(8, 8), fc_width=32)
    params = net.init(jax.random.PRNGKey(10))
    obs = jax.random.normal(jax.random.PRNGKey(11), (5, 6, 6, 2))
    sim = net.apply(NullQATContext(),
                    ptq.ptq_simulate(params, QuantConfig.ptq_int(4)), obs)
    got = actorq.quantized_apply(actorq.pack_actor_params(params, bits=4),
                                 obs, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(sim), atol=2e-2)


def test_int4_interpret_per_layer_matches_ref():
    """The packed-weight (in-kernel unpack) GEMM == the oracle."""
    net = make_network((5,), 3, hidden=(24, 24))
    qp = actorq.pack_actor_params(net.init(jax.random.PRNGKey(12)), bits=4)
    obs = jax.random.normal(jax.random.PRNGKey(13), (7, 5))
    np.testing.assert_allclose(
        np.asarray(actorq.quantized_apply(qp, obs, backend="interpret")),
        np.asarray(actorq.quantized_apply(qp, obs, backend="ref")),
        rtol=1e-5, atol=1e-5)


def test_int4_cache_halves_footprint():
    """ISSUE acceptance: the int4 actor cache is <= ~50% of int8
    ``packed_nbytes`` (codes halve exactly; the shared fp32 biases and
    per-layer affine params keep the total a whisker above half)."""
    net = make_network((9,), 25, hidden=(256, 256, 256))
    params = net.init(jax.random.PRNGKey(14))
    qp8 = actorq.pack_actor_params(params, bits=8)
    qp4 = actorq.pack_actor_params(params, bits=4)
    ratio = actorq.packed_nbytes(qp4) / actorq.packed_nbytes(qp8)
    assert ratio <= 0.55, ratio
    # the codes themselves halve exactly (two int4 per byte, odd-K padded)
    for name in qp8:
        c8, c4 = qp8[name]["w"].codes, qp4[name]["w"].codes
        k, n = c8.shape
        assert c4.shape == ((k + 1) // 2, n)


def test_dequantize_restores_packed_shapes():
    net = make_network((6, 6, 2), 3, conv_filters=(4,), fc_width=16)
    params = net.init(jax.random.PRNGKey(15))
    unpacked = ptq.ptq_unpack(actorq.pack_actor_params(params, bits=4))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(unpacked)):
        assert a.shape == b.shape


# ---------------------------------------------------------------------------
# int4 + static requant in training / deployment
# ---------------------------------------------------------------------------

def test_int4_actor_trains_fused_driver():
    res = loops.train("a2c", "cartpole", iterations=4, record_every=2,
                      eval_episodes=2, steps_per_call=2,
                      actor_backend="int4", calib_batch=8)
    assert all(np.isfinite(res.rewards))
    assert res.algo_cfg.actor_backend == "int4"
    assert res.algo_cfg.calib_batch == 8


def test_int4_actor_learner_topology():
    res = loops.train("dqn", "cartpole", topology="actor-learner",
                      num_actors=2, sync_every=2, actor_backend="int4",
                      calib_batch=8, iterations=4, record_every=2,
                      eval_episodes=2, algo_overrides=dict(SMALL_DQN))
    assert all(np.isfinite(res.rewards))
    assert len(res.divergences) > 0


def test_int4_async_topology_with_calibration():
    res = loops.train("dqn", "cartpole", topology="async", num_actors=2,
                      sync_every=4, steps_per_call=2, actor_backend="int4",
                      calib_batch=8, iterations=4, record_every=2,
                      eval_episodes=2, algo_overrides=dict(SMALL_DQN))
    assert all(np.isfinite(res.rewards))
    assert res.actor_lags and all(lag >= 4 for lag in res.actor_lags)


def test_int4_catch_conv_smoke():
    """Pixel env: the conv GEMM consumes byte-packed int4 codes."""
    res = loops.train("dqn", "catch", iterations=2, record_every=2,
                      eval_episodes=2, actor_backend="int4",
                      net_kwargs=dict(conv_filters=(4,), fc_width=16),
                      algo_overrides=dict(SMALL_DQN))
    assert all(np.isfinite(res.rewards))


def test_eval_policy_int4_deployment():
    res = loops.train("ppo", "cartpole", iterations=6, record_every=6,
                      eval_episodes=2)
    key = jax.random.PRNGKey(0)
    r8 = loops.eval_policy(res, QuantConfig.ptq_int(8), key, episodes=2,
                           actor_backend="int8")
    r4 = loops.eval_policy(res, QuantConfig.ptq_int(4), key, episodes=2,
                           actor_backend="int4")
    assert np.isfinite(r8) and np.isfinite(r4)
    # int4 on an 8-bit quant config caps the packed width at 4
    r_cap = loops.eval_policy(res, QuantConfig.ptq_int(8), key, episodes=2,
                              actor_backend="int4")
    assert np.isfinite(r_cap)


def test_train_rejects_unknown_backend():
    with pytest.raises(ValueError):
        loops.train("a2c", "cartpole", iterations=2,
                    actor_backend="int2")


# ---------------------------------------------------------------------------
# slow: int4 convergence (the sub-8-bit viability claim, Lu et al.)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_int4_calibrated_actor_learner_four_device_mesh():
    """shard_map coverage for the calibrated repack: the cache (incl. the
    static ``act_quant`` scales) is carried replicated over the actor
    axis, so the sync-branch calibration all-gathers its obs batch and
    every device derives identical scales."""
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from repro.rl import loops
        mesh = jax.make_mesh((4,), ("actor",))
        res = loops.train(
            "dqn", "cartpole", topology="actor-learner", num_actors=4,
            sync_every=2, actor_backend="int4", calib_batch=16,
            iterations=4, record_every=2, eval_episodes=2, mesh=mesh,
            algo_overrides=dict(n_envs=4, rollout_steps=4,
                                updates_per_iter=2, buffer_size=1024,
                                batch_size=32, warmup=16,
                                kernel_backend="ref"))
        assert all(np.isfinite(res.rewards)), res.rewards
        assert len(res.divergences) > 0
        print("INT4_CALIB_MESH_OK", res.rewards)
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "INT4_CALIB_MESH_OK" in out.stdout


@pytest.mark.slow
def test_int4_cartpole_dqn_convergence():
    """W4A8 actors with static requant still learn CartPole — the paper's
    bitwidth-sweep claim carried to the true-integer deployment path."""
    res = loops.train("dqn", "cartpole", iterations=400, record_every=50,
                      eval_episodes=8, steps_per_call=5,
                      actor_backend="int4", calib_batch=32, seed=0)
    # random play ~9.5; require clear learning progress
    assert max(res.rewards) > 100.0, res.rewards
