"""MLP policy: QuaRL's deployment policies (arXiv:1910.01055 Table 5),
dense layers with ReLU.

Configuration: ``policy.widths`` (the hidden layers), ``policy.constant``
(the ``quarl_atari`` constant that holds the same widths); the
observation is flattened.

Reference: a list of layers ``{"w", "b"}`` through
``reference.dense_chain``.

Program: ``make_network(hidden=)``, whose tree names the layers ``fc{i}``,
``out``.

Counts: with ``calib_batch`` > 0 the actors' cache is calibrated, and the
whole forward is one ``fused_qmlp`` call; each push then also runs the
calibration pass over ``calib_batch`` observations, one ``int8_matmul``
per layer.  Without it every layer is one ``int8_matmul``.  A push runs the
int8 forward once more (the divergence head), besides its float32
forward.
"""
import jax.numpy as jnp
import numpy as np

import opcount
import reference


def _widths(config):
    return list(config["policy"]["widths"]) + [config["env"]["n_actions"]]


def _obs_dim(config):
    return int(np.prod(config["env"]["obs_shape"]))


def init_weights(key, config, dtype=jnp.float32):
    """N(0, 1/fan_in) weights, zero biases, one key per layer."""
    d, shapes = _obs_dim(config), []
    for wd in _widths(config):
        shapes.append((d, wd))
        d = wd
    return reference.init_layers(key, shapes, dtype)


def forward(weights, config, obs):
    """Q-values ``(B, n_actions)`` for observations ``(B, *obs_shape)``."""
    return reference.dense_chain(weights, obs)


def network_kwargs(config, constant):
    """``make_network``'s hidden widths, which have to be the
    constant's."""
    pol = config["policy"]
    if list(constant.widths) != pol["widths"]:
        raise ValueError(f"quarl_atari.{pol['constant']} is "
                         f"{list(constant.widths)}, the configuration file "
                         f"says {pol['widths']}")
    return dict(hidden=tuple(constant.widths))


def _names(config):
    n = len(config["policy"]["widths"])
    return [f"fc{i}" for i in range(n)] + ["out"]


def to_program(weights, config):
    """The layer list as the program's parameter tree."""
    return dict(zip(_names(config), weights))


def from_program(tree, config):
    """The program's parameter tree as the layer list."""
    return [tree[n] for n in _names(config)]


def gemms(config, n_obs):
    """The GEMMs of one forward pass over ``n_obs`` observations."""
    return opcount.dense_gemms(n_obs, _obs_dim(config), _widths(config))


def rollout_kernels(config, n_obs):
    """One rollout step: the fused kernel on a calibrated cache, else one
    ``int8_matmul`` per layer."""
    gs = gemms(config, n_obs)
    if config["calib_batch"] > 0:
        return {"fused_qmlp": (opcount.gemm_ops(gs),
                               opcount.fused_qmlp_bytes(gs), 1)}
    return opcount.int8_matmul_calls(gs)


def push_kernels(config, n_obs):
    """One push: the int8 divergence head and, on a calibrated cache, the
    calibration pass."""
    out = dict(rollout_kernels(config, n_obs))
    if config["calib_batch"] > 0:
        out.update(opcount.int8_matmul_calls(
            gemms(config, config["calib_batch"])))
    return out


def push_flops(config, n_obs):
    """One push: the float32 divergence head."""
    return opcount.gemm_ops(gemms(config, n_obs))


def learner_flops(config, batch):
    """One learner update of ``batch`` rows."""
    return opcount.chain_learner_flops(gemms(config, batch))
