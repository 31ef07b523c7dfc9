"""Sequence policy: a pre-norm decoder transformer over frame-stacked
observations, acted by int8 actors on a per-environment int8 KV cache.

Configuration: ``policy.d_model``, ``policy.n_layers``, ``policy.d_ff``;
``env.obs_shape`` is ``[context, feat]``, rows oldest first, each ending
in a validity flag (rows from before the episode are all zero).  No
program constant holds these widths, so ``policy.constant`` names none.

Reference, written from that description: an embedding ``x @ w + b`` of
every row; per block an RMS norm (``x / sqrt(mean(x^2) + 1e-6) * (1 +
scale)``), single-head causal attention of width ``d_model`` (scale
``d_model ** -0.5``) over the rows whose flag is set, its output
projection added to the residual, a second RMS norm and a ReLU MLP of
width ``d_ff`` added to the residual; the head ``x @ w + b`` on the
newest row.  Weights N(0, 1/fan_in), biases and norm scales zero, one key
per weight matrix in the order embed, then per block q, k, v, o, fc,
proj, then head.

Program: ``make_network(transformer={d_model, n_layers, d_ff})``, whose
tree has the reference's layout (``embed``, ``blk{i}`` with ``ln1``,
``q``, ``k``, ``v``, ``o``, ``ln2``, ``fc``, ``proj``, and ``head``).

Counts: a rollout step decodes one token per environment, every dense
layer through ``int8_matmul`` and, per block, ``int8_cache_attention``
over the window of ``context`` cached tokens.  A push runs the windowed
int8 head over every row of the frame stack (``int8_matmul``, its
attention in float) and the float32 head; a learner update runs the
windowed forward over ``batch * context`` rows.  Attention is ``4 * S *
d_model`` operations per query over ``S`` keys (scores and values).
"""
import jax
import jax.numpy as jnp
import numpy as np

import opcount

NEG_INF = -1e30
RMS_EPS = 1e-6
DENSE = ("q", "k", "v", "o", "fc", "proj")


def _dims(config):
    pol, env = config["policy"], config["env"]
    s, f = env["obs_shape"]
    return (s, f, pol["d_model"], pol["d_ff"], pol["n_layers"],
            env["n_actions"])


def _block_shapes(d, ff):
    return {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
            "fc": (d, ff), "proj": (ff, d)}


def init_weights(key, config, dtype=jnp.float32):
    """N(0, 1/fan_in) weights; zero biases and norm scales."""
    _, f, d, ff, n_layers, n_act = _dims(config)
    keys = iter(jax.random.split(key, 2 + len(DENSE) * n_layers))

    def dense(shape):
        w = jax.random.normal(next(keys), shape, jnp.float32)
        return {"w": (w / np.sqrt(shape[0])).astype(dtype),
                "b": jnp.zeros((shape[1],), dtype)}

    tree = {"embed": dense((f, d))}
    for i in range(n_layers):
        shapes = _block_shapes(d, ff)
        blk = {name: dense(shapes[name]) for name in DENSE}
        blk["ln1"] = {"scale": jnp.zeros((d,), dtype)}
        blk["ln2"] = {"scale": jnp.zeros((d,), dtype)}
        tree[f"blk{i}"] = blk
    tree["head"] = dense((d, n_act))
    return tree


def _rms(p, x):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * (1 + p["scale"])


def _lin(p, x):
    return x @ p["w"] + p["b"]


def forward(weights, config, obs):
    """Q-values ``(B, n_actions)`` for frame stacks ``(B, S, F)``."""
    s = obs.shape[-2]
    d = config["policy"]["d_model"]
    valid = obs[..., -1] > 0.5
    mask = jnp.tril(jnp.ones((s, s), bool)) & valid[:, None, :]
    x = _lin(weights["embed"], obs)
    for i in range(config["policy"]["n_layers"]):
        blk = weights[f"blk{i}"]
        h = _rms(blk["ln1"], x)
        q, k, v = (_lin(blk[n], h) for n in ("q", "k", "v"))
        logits = jnp.einsum("bsd,btd->bst", q, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, logits, NEG_INF), axis=-1)
        x = x + _lin(blk["o"], jnp.einsum("bst,btd->bsd", p, v))
        h = _rms(blk["ln2"], x)
        x = x + _lin(blk["proj"], jax.nn.relu(_lin(blk["fc"], h)))
    return _lin(weights["head"], x[:, -1])


def network_kwargs(config, constant):
    """``make_network``'s transformer sizes (the configuration's own)."""
    if constant is not None:
        raise ValueError("the program holds no constant for a sequence "
                         "policy's widths; the configuration names one")
    pol = config["policy"]
    return {"transformer": {k: pol[k] for k in ("d_model", "n_layers",
                                                "d_ff")}}


def to_program(weights, config):
    """The program's tree has the reference's layout."""
    return weights


def from_program(tree, config):
    """The program's tree has the reference's layout."""
    return tree


def _gemms(config, rows, head_rows):
    _, f, d, ff, n_layers, n_act = _dims(config)
    shapes = _block_shapes(d, ff)
    gs = [(rows, f, d)]
    for _ in range(n_layers):
        gs += [(rows, *shapes[n]) for n in DENSE]
    return gs + [(head_rows, d, n_act)]


def _attention_ops(config, queries, keys_per_query):
    _, _, d, _, n_layers, _ = _dims(config)
    return float(n_layers * queries * 4 * keys_per_query * d)


def rollout_kernels(config, n_obs):
    """One decode step: one ``int8_matmul`` per dense layer, and per
    block one ``int8_cache_attention`` over the window: the query and
    output in float32, the window's int8 codes and float32 scales."""
    s, _, d, _, n_layers, _ = _dims(config)
    out = opcount.int8_matmul_calls(_gemms(config, n_obs, n_obs))
    out["int8_cache_attention"] = (
        _attention_ops(config, n_obs, s),
        float(n_layers * n_obs * (2 * s * d + 8 * s + 8 * d)), n_layers)
    return out


def push_kernels(config, n_obs):
    """One push: the windowed int8 divergence head."""
    s = config["env"]["obs_shape"][0]
    return opcount.int8_matmul_calls(_gemms(config, n_obs * s, n_obs))


def push_flops(config, n_obs):
    """One push: the float32 head, and the int8 head's attention."""
    s = config["env"]["obs_shape"][0]
    return (opcount.gemm_ops(_gemms(config, n_obs * s, n_obs))
            + 2 * _attention_ops(config, n_obs * s, s))


def learner_flops(config, batch):
    """One learner update of ``batch`` frame stacks: the online forward,
    its backward (all but the embedding's input gradient) and the target's
    forward."""
    s = config["env"]["obs_shape"][0]
    gs = _gemms(config, batch * s, batch)
    m, k, n = gs[0]
    fwd = opcount.gemm_ops(gs) + _attention_ops(config, batch * s, s)
    return 4 * fwd - 2 * m * k * n
