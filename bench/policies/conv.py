"""Conv policy: QuaRL's Atari backbone (arXiv:1910.01055 Appendix B,
Table 10 Policies A-C), 3x3 stride-1 SAME convs and one hidden dense
layer.

Configuration: ``policy.conv_filters`` (one entry per conv),
``policy.fc_width``, ``policy.constant`` (the ``quarl_atari`` constant that
holds the same widths); ``env.obs_shape`` is ``[H, W, C]``.

Reference: a list of layers ``{"w", "b"}``, the convs (HWIO weights, NHWC
activations, ReLU), then the dense layers (``reference.dense_chain``) on
the last conv's activations flattened in (H, W, C) order.

Program: ``make_network(conv_filters=, fc_width=)``, whose tree names the
layers ``conv{i}``, ``fc``, ``out``.

Counts: the int8 actor runs every layer through ``int8_matmul``, a conv as
the GEMM of its tap-major int8 operand, ``M = n * H * W`` rows of ``K =
9 * C_in``.  The cache is never calibrated, so a push runs the same
forward once more (the divergence head), besides its float32 forward.
"""
import jax
import jax.numpy as jnp

import opcount
import reference


def _shapes(config):
    pol, env = config["policy"], config["env"]
    h, w, c = env["obs_shape"]
    out = []
    for f in pol["conv_filters"]:
        out.append((3, 3, c, f))
        c = f
    d = h * w * c
    for wd in [pol["fc_width"], env["n_actions"]]:
        out.append((d, wd))
        d = wd
    return out


def _names(config):
    n = len(config["policy"]["conv_filters"])
    return [f"conv{i}" for i in range(n)] + ["fc", "out"]


def init_weights(key, config, dtype=jnp.float32):
    """N(0, 1/fan_in) weights, zero biases, one key per layer."""
    return reference.init_layers(key, _shapes(config), dtype)


def forward(weights, config, obs):
    """Q-values ``(B, n_actions)`` for observations ``(B, H, W, C)``."""
    n = len(config["policy"]["conv_filters"])
    x = obs
    for p in weights[:n]:
        x = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        x = jax.nn.relu(x)
    x = x.reshape(x.shape[0], -1)
    return reference.dense_chain(weights[n:], x)


def network_kwargs(config, constant):
    """``make_network``'s conv widths, which have to be the constant's."""
    pol = config["policy"]
    got = {"conv_filters": list(constant.conv_filters),
           "fc_width": constant.fc_width}
    want = {"conv_filters": pol["conv_filters"], "fc_width": pol["fc_width"]}
    if got != want:
        raise ValueError(f"quarl_atari.{pol['constant']} is {got}, the "
                         f"configuration file says {want}")
    return dict(conv_filters=tuple(constant.conv_filters),
                fc_width=constant.fc_width)


def to_program(weights, config):
    """The layer list as the program's parameter tree."""
    return dict(zip(_names(config), weights))


def from_program(tree, config):
    """The program's parameter tree as the layer list."""
    return [tree[n] for n in _names(config)]


def gemms(config, n_obs):
    """The GEMMs of one forward pass over ``n_obs`` observations."""
    pol, env = config["policy"], config["env"]
    h, w, c = env["obs_shape"]
    out = []
    for f in pol["conv_filters"]:
        out.append((n_obs * h * w, 9 * c, f))
        c = f
    return out + opcount.dense_gemms(n_obs, h * w * c,
                                     [pol["fc_width"], env["n_actions"]])


def rollout_kernels(config, n_obs):
    """One rollout step: one ``int8_matmul`` per layer."""
    return opcount.int8_matmul_calls(gemms(config, n_obs))


def push_kernels(config, n_obs):
    """One push: the int8 divergence head, the rollout's forward."""
    return rollout_kernels(config, n_obs)


def push_flops(config, n_obs):
    """One push: the float32 divergence head."""
    return opcount.gemm_ops(gemms(config, n_obs))


def learner_flops(config, batch):
    """One learner update of ``batch`` rows."""
    return opcount.chain_learner_flops(gemms(config, batch))
