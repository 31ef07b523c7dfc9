"""Native-XLA int8 backend vs the pure-jnp oracle (ISSUE 6).

Acceptance contracts:
* xla-vs-ref *bitwise* parity of ``ops.int8_matmul`` across bits {4, 8} x
  odd/even K (the int4 padding edge) x chunked K (contractions longer than
  the exact-f32 bound, exercising the int32 chunk accumulator),
* the same parity for the per-layer and fused actor applies across heads
  {logits, q, mu} and for the conv path (Catch pixel actors, tap-major
  int8 patches),
* ``_resolve``: ``auto`` -> ``xla`` off-TPU, the ``REPRO_KERNEL_BACKEND``
  env override, and explicit ``backend=`` always winning,
* the 8-bit branch rejects K-mismatched weights with a ``ValueError``
  (regression: it used to contract garbage silently),
* int8 + ``kernel_backend="xla"`` trains end to end on every topology.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import affine, ptq
from repro.core.qconfig import QuantConfig
from repro.kernels import ops, ref, xla_backend
from repro.rl import actorq, loops
from repro.rl.networks import make_network

SMALL_DQN = dict(n_envs=4, rollout_steps=4, updates_per_iter=2,
                 buffer_size=512, batch_size=16, warmup=8)


# ---------------------------------------------------------------------------
# int8_matmul: xla vs ref, bitwise
# ---------------------------------------------------------------------------

def _operands(key, m, k, n, bits):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k)) * 2.0
    w = jax.random.normal(kw, (k, n)) * 0.5
    xq, xp = affine.quantize_to_int(x, 8, axis=None)
    wq, wp = affine.quantize_to_int(w, bits, axis=1)
    return xq, xp, wq, wp


# odd/even K, K=1 edge, and K=700 > the 8-bit exact-f32 chunk (258) so the
# CPU path must take the chunked int32 accumulator
@pytest.mark.parametrize("mkn", [(9, 64, 32), (9, 65, 32), (7, 33, 5),
                                 (1, 1, 8), (5, 700, 16)])
@pytest.mark.parametrize("bits", [4, 8])
def test_int8_matmul_xla_bitwise_matches_ref(mkn, bits):
    m, k, n = mkn
    xq, xp, wq, wp = _operands(jax.random.PRNGKey(m * 131 + k + bits),
                               m, k, n, bits)
    w_scale = wp.delta.reshape(-1)
    w_zero = wp.zero_point.reshape(-1)
    want = ref.int8_matmul_ref(xq, wq, xp.delta, w_scale, xp.zero_point,
                               w_zero)
    w_arg = affine.pack_int4(wq) if bits <= 4 else wq
    got = ops.int8_matmul(xq, w_arg, xp.delta, xp.zero_point, w_scale,
                          w_zero, backend="xla", w_bits=bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_exact_f32_matmul_chunks_like_int32():
    """Adversarial contraction: worst-case magnitude codes at K well past
    the exact-f32 bound still reproduce int32 accumulation exactly."""
    k = 3000
    xq = jnp.full((2, k), -128, jnp.int8)
    wq = jnp.full((k, 3), 127, jnp.int8)
    xc = xq.astype(jnp.float32) - (-3.0)
    wc = wq.astype(jnp.float32) - 2.0
    got = xla_backend._exact_f32_matmul(xc, wc, 8)
    want = (np.asarray(xc).astype(np.int64) @ np.asarray(wc).astype(np.int64)
            ).astype(np.int32).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# actor applies: per-layer + fused + conv, xla vs ref, bitwise
# ---------------------------------------------------------------------------

_HEAD_OUT = {"logits": 4, "q": 3, "mu": 2}   # a2c/ppo (+value), dqn, ddpg


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("head", sorted(_HEAD_OUT))
@pytest.mark.parametrize("fused", [False, True])
def test_actor_apply_xla_bitwise_matches_ref(bits, head, fused):
    net = make_network((5,), _HEAD_OUT[head], hidden=(24, 24))
    params = net.init(jax.random.PRNGKey(bits + len(head)))
    obs = jax.random.normal(jax.random.PRNGKey(7), (9, 5)) * 2.0
    qp = actorq.pack_actor_params(params, bits=bits)
    if fused:
        qp = actorq.calibrate_actor_cache(qp, obs, backend="ref")
        assert actorq.ACT_QUANT in qp
    got = actorq.quantized_apply(qp, obs, backend="xla")
    want = actorq.quantized_apply(qp, obs, backend="ref")
    assert got.shape == (9, _HEAD_OUT[head])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", [4, 8])
def test_conv_im2col_xla_bitwise_matches_ref(bits):
    net = make_network((6, 6, 2), 3, conv_filters=(4,), fc_width=16)
    qp = actorq.pack_actor_params(net.init(jax.random.PRNGKey(3)), bits=bits)
    obs = jax.random.normal(jax.random.PRNGKey(4), (5, 6, 6, 2))
    np.testing.assert_array_equal(
        np.asarray(actorq.quantized_apply(qp, obs, backend="xla")),
        np.asarray(actorq.quantized_apply(qp, obs, backend="ref")))


@pytest.mark.parametrize("backend", ["ref", "xla", "interpret"])
def test_int4_conv_cache_round_trips(backend):
    """A packed int4 conv weight is HWIO reshaped tap-major: dequantize
    restores HWIO, and the W4A8 conv equals the W8A8 conv over the
    unpacked codes."""
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 3, 3, 8))
    packed = ptq.ptq_pack({"w": w}, QuantConfig.ptq_int(4))["w"]
    codes, p = affine.quantize_to_int(w, 4, axis=3)
    assert packed.codes.shape == ((3 * 3 * 3 + 1) // 2, 8)
    np.testing.assert_array_equal(
        np.asarray(packed.unpacked_codes().reshape(w.shape)),
        np.asarray(codes))
    np.testing.assert_array_equal(
        np.asarray(packed.dequantize()),
        np.asarray(affine.dequantize_from_int(codes, p)))
    # the same codes unpacked, in HWIO, through the 8-bit GEMM path
    w8 = packed._replace(codes=codes, bits=8, orig_shape=None)
    b = jnp.linspace(-0.1, 0.1, 8)
    x = jax.random.normal(jax.random.PRNGKey(6), (2 * 5 * 4, 3))
    got = actorq.int8_conv2d({"w": packed, "b": b}, x, (5, 4),
                             backend=backend)
    want = actorq.int8_conv2d({"w": w8, "b": b}, x, (5, 4), backend=backend)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _channel_major(tensor):
    """A packed int4 conv weight with its codes in the channel-major
    ``(C_in*kh*kw, C_out)`` K order, as a checkpoint may hold them."""
    kh, kw, ci, co = tensor.orig_shape
    codes = tensor.unpacked_codes().reshape(kh, kw, ci, co)
    codes = codes.transpose(2, 0, 1, 3).reshape(-1, co)
    return tensor._replace(codes=affine.pack_int4(codes))


@pytest.mark.parametrize("actor_backend", ["int4", "int8"])
def test_restored_conv_cache_is_repacked_tap_major(actor_backend):
    """A restored int4 conv cache is re-minted from its params: codes in
    the channel-major order come back tap-major, and a cache already in
    it comes back the same.  An int8 cache, whose order never changed,
    and an MLP cache are returned as restored."""
    net = make_network((6, 5, 1), 3, conv_filters=(4, 8), fc_width=16)
    params = net.init(jax.random.PRNGKey(8))
    fresh = actorq.make_actor_cache(params, actor_backend)
    obs = jax.random.normal(jax.random.PRNGKey(9), (3, 6, 5, 1))
    want = actorq.quantized_apply(fresh, obs, backend="ref")
    if actor_backend == "int8":
        assert actorq.repack_restored_cache(fresh, params, "int8") is fresh
        mlp = make_network((5,), 3, hidden=(8,)).init(jax.random.PRNGKey(1))
        mlp_cache = actorq.make_actor_cache(mlp, "int4")
        assert actorq.repack_restored_cache(mlp_cache, mlp,
                                            "int4") is mlp_cache
        return
    old = {name: ({**layer, "w": _channel_major(layer["w"])}
                  if name.startswith("conv") else layer)
           for name, layer in fresh.items()}
    assert not np.array_equal(np.asarray(old["conv1"]["w"].codes),
                              np.asarray(fresh["conv1"]["w"].codes))
    for restored in (old, fresh):
        got = actorq.repack_restored_cache(restored, params, "int4")
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(actorq.quantized_apply(got, obs, backend="ref")),
            np.asarray(want))


# ---------------------------------------------------------------------------
# dispatch: auto resolution + REPRO_KERNEL_BACKEND override
# ---------------------------------------------------------------------------

def test_auto_resolves_to_xla_off_tpu(monkeypatch):
    monkeypatch.delenv(ops.ENV_BACKEND, raising=False)
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert ops._resolve("auto") == want


@pytest.mark.parametrize("forced", ops.BACKENDS)
def test_env_override_forces_backend(monkeypatch, forced):
    monkeypatch.setenv(ops.ENV_BACKEND, forced)
    assert ops._resolve("auto") == forced


def test_explicit_backend_beats_env_override(monkeypatch):
    monkeypatch.setenv(ops.ENV_BACKEND, "ref")
    assert ops._resolve("interpret") == "interpret"


def test_env_override_rejects_unknown_backend(monkeypatch):
    monkeypatch.setenv(ops.ENV_BACKEND, "cuda")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        ops._resolve("auto")


def test_env_override_reaches_jitted_op(monkeypatch):
    """The override must bite inside a jitted ``backend="auto"`` call.  An
    off-pattern shape keeps this trace out of the shared jit cache (the
    env var is read at trace time, so a cached entry would shadow it)."""
    monkeypatch.setenv(ops.ENV_BACKEND, "ref")
    xq, xp, wq, wp = _operands(jax.random.PRNGKey(0), 3, 17, 11, 8)
    got = ops.int8_matmul(xq, wq, xp.delta, xp.zero_point,
                          wp.delta.reshape(-1), wp.zero_point.reshape(-1))
    want = ref.int8_matmul_ref(xq, wq, xp.delta, wp.delta.reshape(-1),
                               xp.zero_point, wp.zero_point.reshape(-1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# shape validation (regression: 8-bit branch accepted K-mismatched weights)
# ---------------------------------------------------------------------------

def test_int8_matmul_rejects_k_mismatched_weights():
    xq, xp, wq, wp = _operands(jax.random.PRNGKey(1), 4, 32, 8, 8)
    with pytest.raises(ValueError, match="unpacked codes"):
        ops.int8_matmul(xq, wq[:-1], xp.delta, xp.zero_point,
                        wp.delta.reshape(-1), wp.zero_point.reshape(-1),
                        backend="ref")


def test_int8_matmul_rejects_packed_codes_without_w_bits():
    """A byte-packed int4 cache passed with the default w_bits=8 is the
    silent-garbage case the validation exists for."""
    xq, xp, wq, wp = _operands(jax.random.PRNGKey(2), 4, 32, 8, 4)
    packed = affine.pack_int4(wq)
    with pytest.raises(ValueError, match="byte-packed"):
        ops.int8_matmul(xq, packed, xp.delta, xp.zero_point,
                        wp.delta.reshape(-1), wp.zero_point.reshape(-1),
                        backend="ref")
    with pytest.raises(ValueError, match="byte-packed codes"):
        ops.int8_matmul(xq, wq, xp.delta, xp.zero_point,
                        wp.delta.reshape(-1), wp.zero_point.reshape(-1),
                        backend="ref", w_bits=4)


# ---------------------------------------------------------------------------
# training smokes: kernel_backend="xla" on every topology
# ---------------------------------------------------------------------------

def test_int8_xla_trains_fused_driver():
    res = loops.train("a2c", "cartpole", iterations=4, record_every=2,
                      eval_episodes=2, steps_per_call=2,
                      actor_backend="int8", calib_batch=8,
                      algo_overrides=dict(kernel_backend="xla"))
    assert all(np.isfinite(res.rewards))
    assert res.algo_cfg.kernel_backend == "xla"


def test_int8_xla_actor_learner_topology():
    res = loops.train("dqn", "cartpole", topology="actor-learner",
                      num_actors=2, sync_every=2, actor_backend="int8",
                      iterations=4, record_every=2, eval_episodes=2,
                      algo_overrides=dict(SMALL_DQN, kernel_backend="xla"))
    assert all(np.isfinite(res.rewards))
    assert len(res.divergences) > 0


def test_int8_xla_async_topology():
    res = loops.train("dqn", "cartpole", topology="async", num_actors=2,
                      sync_every=4, steps_per_call=2, actor_backend="int8",
                      calib_batch=8, iterations=4, record_every=2,
                      eval_episodes=2,
                      algo_overrides=dict(SMALL_DQN, kernel_backend="xla"))
    assert all(np.isfinite(res.rewards))
    assert res.actor_lags and all(lag >= 4 for lag in res.actor_lags)
