"""ActorQ tests: true int8 actor inference + the scan-fused training driver.

Acceptance contract (ISSUE 1):
* the int8 path (``backend="ref"`` on CPU) agrees with the fake-quant fp32
  actor within atol=1e-2 on MLP and CNN policies,
* the scan-fused driver is numerically equivalent to the per-step driver
  (same seed -> same final params, bitwise on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affine, ptq
from repro.core.fake_quant import NullQATContext
from repro.core.qconfig import QuantConfig
from repro.kernels import ops
from repro.rl import actorq, loops
from repro.rl.envs import make as make_env
from repro.rl.networks import make_network


# ---------------------------------------------------------------------------
# int8 actor vs fake-quant fp32 actor
# ---------------------------------------------------------------------------

def _fake_quant_outputs(net, params, obs):
    """The fp32 simulation the repo used before ActorQ (same quantizer)."""
    sim = ptq.ptq_simulate(params, QuantConfig.ptq_int(8))
    return net.apply(NullQATContext(), sim, obs)


def test_int8_mlp_matches_fake_quant_actor():
    net = make_network((4,), 2)
    params = net.init(jax.random.PRNGKey(0))
    obs = jax.random.normal(jax.random.PRNGKey(1), (32, 4)) * 2.0
    want = _fake_quant_outputs(net, params, obs)
    got = actorq.quantized_apply(actorq.pack_actor_params(params), obs,
                                 backend="ref")
    np.testing.assert_allclose(got, want, atol=1e-2)


def test_int8_cnn_matches_fake_quant_actor():
    net = make_network((6, 6, 2), 3, conv_filters=(8, 8), fc_width=32)
    params = net.init(jax.random.PRNGKey(2))
    obs = jax.random.normal(jax.random.PRNGKey(3), (5, 6, 6, 2))
    want = _fake_quant_outputs(net, params, obs)
    got = actorq.quantized_apply(actorq.pack_actor_params(params), obs,
                                 backend="ref")
    np.testing.assert_allclose(got, want, atol=1e-2)


def test_int8_interpret_kernel_matches_ref_oracle():
    """The Pallas kernel path (interpret on CPU) == the pure-jnp oracle."""
    net = make_network((4,), 2)
    params = net.init(jax.random.PRNGKey(4))
    obs = jax.random.normal(jax.random.PRNGKey(5), (16, 4))
    qp = actorq.pack_actor_params(params)
    ref = actorq.quantized_apply(qp, obs, backend="ref")
    interp = actorq.quantized_apply(qp, obs, backend="interpret")
    np.testing.assert_allclose(interp, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# int8 conv: tap-major operand vs the channel-major im2col lowering
# ---------------------------------------------------------------------------

def _im2col_conv(layer, x, backend):
    """The channel-major im2col lowering, as the oracle: f32 patches in
    ``(C_in, kh, kw)`` feature order, quantized per-tensor, against HWIO
    codes transposed to that order, through the same GEMM."""
    w = layer["w"]
    kh, kw, c_in, c_out = w.codes.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    pq, pp = affine.quantize_to_int(
        patches.reshape(-1, patches.shape[-1]), 8)
    w2 = jnp.transpose(w.codes, (2, 0, 1, 3)).reshape(-1, c_out)
    y = ops.int8_matmul(pq, w2, pp.delta, pp.zero_point, w.col_scale,
                        w.col_zero, backend=backend)
    return jax.nn.relu(y + layer["b"])


_CONV_CASES = {
    # name: (batch dims, H, W, C_in, sign of the input)
    "c1": ((4,), 6, 4, 1, 0),
    "c2_lead_dims": ((2, 3), 3, 5, 2, 0),
    "c128": ((2,), 4, 3, 128, 0),
    "c2_nonneg": ((3,), 5, 4, 2, 1),
    # all non-positive: z = 256 clips to code 127, so the pad code is not
    # the stored zero point
    "c2_nonpos": ((3,), 4, 5, 2, -1),
}


@pytest.mark.parametrize("backend", ["ref", "xla", "interpret"])
@pytest.mark.parametrize("case", sorted(_CONV_CASES))
def test_int8_conv_tap_major_matches_im2col(case, backend):
    batch, h, w, c_in, sign = _CONV_CASES[case]
    kw_, kx, kb = jax.random.split(jax.random.PRNGKey(c_in + h), 3)
    layer = ptq.ptq_pack(
        {"w": jax.random.normal(kw_, (3, 3, c_in, 8)),
         "b": jax.random.normal(kb, (8,)) * 0.1}, QuantConfig.ptq_int(8))
    x = jax.random.normal(kx, batch + (h, w, c_in)) * 2.0
    if sign:
        x = sign * jnp.abs(x)
    if sign < 0:
        p = affine.calibration_params(x, 8)
        assert float(p.zero_point) > 127.0     # the clipping case
    got = actorq.int8_conv2d(layer, x.reshape(-1, c_in), (h, w),
                             backend=backend)
    want = _im2col_conv(layer, x.reshape((-1, h, w, c_in)), backend)
    assert got.shape == (want.shape[0], 8)
    if backend == "interpret":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_actor_is_4x_smaller():
    net = make_network((9,), 25, hidden=(256, 256, 256))
    params = net.init(jax.random.PRNGKey(6))
    qp = actorq.pack_actor_params(params)
    assert actorq.packed_nbytes(qp) < ptq.tree_nbytes(params) / 3.0


def test_make_act_fn_heads():
    # discrete: argmax over action logits, value head sliced off
    env = make_env("cartpole")
    net = make_network(env.spec.obs_shape, env.spec.n_actions + 1)
    qp = actorq.pack_actor_params(net.init(jax.random.PRNGKey(7)))
    act = actorq.make_act_fn(env.spec, backend="ref")
    obs = jax.random.normal(jax.random.PRNGKey(8), (10, 4))
    a = act(qp, obs)
    assert a.dtype == jnp.int32 and a.shape == (10,)
    assert int(a.max()) < env.spec.n_actions
    # continuous: tanh * action_scale
    penv = make_env("pendulum")
    pnet = make_network(penv.spec.obs_shape, penv.spec.action_dim)
    pqp = actorq.pack_actor_params(pnet.init(jax.random.PRNGKey(9)))
    pact = actorq.make_act_fn(penv.spec, backend="ref")
    pa = pact(pqp, jax.random.normal(jax.random.PRNGKey(10), (10, 3)))
    assert pa.shape == (10, 1)
    assert float(jnp.abs(pa).max()) <= penv.spec.action_scale + 1e-6


def test_validate_actor_backend():
    # "int4" joined the backend matrix in PR 5; junk strings still fail in
    # the one shared validator every entry point routes through
    assert actorq.validate_actor_backend("int8") == "int8"
    assert actorq.validate_actor_backend("int4") == "int4"
    for bad in ("int2", "INT8", "", "fp16"):
        with pytest.raises(ValueError):
            actorq.validate_actor_backend(bad)
    assert actorq.backend_bits("int8") == 8
    assert actorq.backend_bits("int4") == 4
    with pytest.raises(ValueError):
        actorq.backend_bits("fp32")       # quantized backends only
    assert actorq.is_quantized("int4") and not actorq.is_quantized("fp32")


def test_pack_actor_params_rejects_bad_bits():
    """ValueError (not assert — asserts vanish under ``python -O``)."""
    net = make_network((4,), 2)
    params = net.init(jax.random.PRNGKey(0))
    for bad in (9, 0, -1, 16):
        with pytest.raises(ValueError):
            actorq.pack_actor_params(params, bits=bad)


# ---------------------------------------------------------------------------
# scan-fused driver
# ---------------------------------------------------------------------------

def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("algo,env", [("a2c", "cartpole"),
                                      ("dqn", "cartpole")])
def test_scan_fused_driver_bitwise_equivalent(algo, env):
    kw = dict(iterations=8, record_every=4, eval_episodes=2, seed=7)
    per_step = loops.train(algo, env, steps_per_call=1, **kw)
    fused = loops.train(algo, env, steps_per_call=4, **kw)
    for a, b in zip(_leaves(per_step.state.params),
                    _leaves(fused.state.params)):
        np.testing.assert_array_equal(a, b)
    assert per_step.rewards == fused.rewards        # same eval PRNG chain
    assert per_step.action_variances == fused.action_variances


def test_scan_fused_chunks_clip_to_record_boundaries():
    # steps_per_call larger than record_every: chunks clip, records match
    kw = dict(iterations=6, record_every=3, eval_episodes=2, seed=1)
    a = loops.train("a2c", "cartpole", steps_per_call=1, **kw)
    b = loops.train("a2c", "cartpole", steps_per_call=100, **kw)
    assert a.rewards == b.rewards
    for x, y in zip(_leaves(a.state.params), _leaves(b.state.params)):
        np.testing.assert_array_equal(x, y)


def test_make_scan_iteration_stacks_metrics():
    from repro.rl import a2c
    env = make_env("cartpole")
    cfg = a2c.A2CConfig(n_envs=4, n_steps=4)
    net = make_network(env.spec.obs_shape, env.spec.n_actions + 1)
    state = a2c.init(jax.random.PRNGKey(0), env, net, cfg)
    iteration, _, benv = a2c.make_iteration(env, net, cfg)
    env_state, obs = benv.reset(jax.random.PRNGKey(1))
    chunk = loops.make_scan_iteration(iteration, 3)
    state, env_state, obs, key, metrics = chunk(state, env_state, obs,
                                                jax.random.PRNGKey(2))
    assert metrics["loss"].shape == (3,)
    assert bool(jnp.all(jnp.isfinite(metrics["loss"])))


# ---------------------------------------------------------------------------
# int8 actor in training + deployment
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["a2c", "dqn"])
def test_int8_actor_rollouts_train_finite(algo):
    res = loops.train(algo, "cartpole", iterations=6, record_every=3,
                      eval_episodes=2, steps_per_call=3,
                      actor_backend="int8")
    assert all(np.isfinite(res.rewards))
    assert res.algo_cfg.actor_backend == "int8"


def test_eval_policy_int8_deployment():
    res = loops.train("ppo", "cartpole", iterations=10, record_every=10,
                      eval_episodes=2)
    key = jax.random.PRNGKey(0)
    r_sim = loops.eval_policy(res, QuantConfig.ptq_int(8), key, episodes=4)
    r_int8 = loops.eval_policy(res, QuantConfig.ptq_int(8), key, episodes=4,
                               actor_backend="int8")
    assert np.isfinite(r_sim) and np.isfinite(r_int8)


def test_eval_policy_int8_ddpg_actor_only():
    """DDPG deployment packs only the actor — the critic stays in extras."""
    res = loops.train("ddpg", "pendulum", iterations=4, record_every=4,
                      eval_episodes=2)
    qp = actorq.pack_actor_params(res.state.params)
    # packed tree mirrors the actor MLP spec exactly (no critic keys)
    assert set(qp) == set(res.state.params)
    r = loops.eval_policy(res, QuantConfig.ptq_int(8), jax.random.PRNGKey(1),
                          episodes=2, actor_backend="int8")
    assert np.isfinite(r)


def test_conv_quant_delay_respected():
    """conv2d honours ctx.enabled (the old hasattr guard silently skipped
    the quant_delay gate for contexts without the attribute)."""
    from repro.core import fake_quant
    cfg = QuantConfig.qat(8, quant_delay=10)
    net = make_network((6, 6, 2), 3, conv_filters=(4,), fc_width=16)
    params = net.init(jax.random.PRNGKey(0))
    obs = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 6, 2))
    before = net.apply(fake_quant.make_context(cfg, {}, step=0), params, obs)
    plain = net.apply(NullQATContext(), params, obs)
    np.testing.assert_allclose(before, plain, rtol=1e-6)   # delay: identity
    after = net.apply(fake_quant.make_context(cfg, {}, step=10), params, obs)
    assert not np.allclose(after, plain)                   # quant active
