"""The main path's kernels compile for a TPU v5e chip that is described,
not attached.

The TPU compiler is installed with jaxlib, so these tests compile each
Pallas kernel of the ActorQ hot path at real widths for ``v5e:2x2``'s first
chip without one: what the chip's compiler refuses (an unsupported op, a
block shape that breaks the tiling, too much VMEM) fails here, at no chip
time.  Nothing runs, so results are checked elsewhere (the ``ref``
parity tests, and ``chip_smoke.py`` on the chip).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every pytest-xdist worker imports every test file.  Keep all such
compiles in this one file, so that one worker holds the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.quarl_atari import DEPLOY_POLICY_II, DEPLOY_POLICY_III
from repro.kernels import ops
from repro.rl import actorq
from repro.rl.networks import make_network


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the failure, it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of ``tree`` placed on ``sharding`` (arrays or shape structs)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("w_bits", [8, 4])
@pytest.mark.parametrize("k", [4, 9, 1152, 4096])
def test_int8_matmul_compiles(one_chip, w_bits, k):
    m, n = 1024, 256
    w_rows = (k + 1) // 2 if w_bits <= 4 else k
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((m, k), jnp.int8),
        jax.ShapeDtypeStruct((w_rows, n), jnp.int8),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32)))
    _compile(lambda *a: ops.int8_matmul(*a, backend="pallas",
                                        w_bits=w_bits), *args)


_FUSED_CELLS = [
    # (policy, obs dim, actions, batch): cartpole and the AirNav deployment
    (DEPLOY_POLICY_II, 4, 2, 8),
    (DEPLOY_POLICY_II, 4, 2, 1024),
    (DEPLOY_POLICY_III, 9, 25, 1),
    (DEPLOY_POLICY_III, 9, 25, 256),
]


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("policy,obs_dim,n_out,batch", _FUSED_CELLS,
                         ids=lambda v: getattr(v, "name", str(v)))
def test_fused_qmlp_compiles(one_chip, bits, policy, obs_dim, n_out, batch):
    net = make_network((obs_dim,), n_out, hidden=policy.widths)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    obs = jax.ShapeDtypeStruct((batch, obs_dim), jnp.float32)
    cache = jax.eval_shape(
        lambda p, o: actorq.calibrate_actor_cache(
            actorq.pack_actor_params(p, bits=bits), o, backend="ref"),
        params, obs)
    assert actorq.ACT_QUANT in cache
    _compile(lambda c, o: actorq.quantized_apply(c, o, backend="pallas"),
             *_on(one_chip, (cache, obs)))


@pytest.mark.parametrize("pos_shape", [(), (64,)], ids=["shared", "ragged"])
def test_int8_cache_attention_compiles(one_chip, pos_shape):
    b, g, t, dh = 64, 4, 128, 64
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((b, g, dh), jnp.float32),
        jax.ShapeDtypeStruct((b, t, dh), jnp.int8),
        jax.ShapeDtypeStruct((b, t, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, t, dh), jnp.int8),
        jax.ShapeDtypeStruct((b, t, 1), jnp.float32),
        jax.ShapeDtypeStruct(pos_shape, jnp.int32)))
    _compile(lambda *a: ops.int8_cache_attention(*a, backend="pallas"),
             *args)


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((8, 256, 128), jnp.float32)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, backend="pallas"),
             *_on(one_chip, (q, q, q)))


@pytest.mark.parametrize("bits", [8, 4])
def test_int8_conv2d_policy_c_compiles_tap_major(one_chip, bits):
    """Policy C's 1024-channel conv over 64 observations of 10x10, with
    int8 (W8A8) and byte-packed int4 (W4A8) weight codes: the GEMM
    operand is the dense tap-major ``s8[6400, 9216]``, built from the
    int8 codes with no lane-padded ``[..., 9]`` patch array, no patch
    convolution and at most twice that operand in temporaries."""
    n, h, w, c = 64, 10, 10, 1024
    layer = jax.eval_shape(lambda: actorq.pack_actor_params(
        {"w": jnp.zeros((3, 3, c, c)), "b": jnp.zeros((c,))}, bits))
    x = jax.ShapeDtypeStruct((n * h * w, c), jnp.float32)
    compiled = _compile(
        lambda lay, x: actorq.int8_conv2d(lay, x, (h, w), backend="pallas"),
        *_on(one_chip, (layer, x)))
    text = compiled.as_text()
    assert not re.search(r"s8\[[0-9,]*,9\]", text)
    assert "convolution(" not in text
    assert "conv_general_dilated_patches" not in text
    operand = n * h * w * 9 * c
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * operand
