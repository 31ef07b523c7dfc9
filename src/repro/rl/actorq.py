"""ActorQ: true int8 actor inference for the RL hot path.

The paper's headline systems result is that 8-bit *actors* collect data
1.5-5.41x faster without hurting convergence.  Everywhere else in this repo
quantization is *simulated* (fake-quant in fp32); this module is the real
thing: policy parameters are packed once per learner update into an int8
cache (``pack_actor_params``), and every dense layer of the actor forward
pass runs through the W8A8 integer GEMM in ``repro.kernels`` —
``lax.dot_general`` over int8 codes with int32 accumulation and a fused
affine-dequant epilogue (Pallas on TPU, the native-XLA integer backend in
``kernels.xla_backend`` everywhere else).

Quantization scheme (matches ``core.ptq`` exactly, so the int8 path and the
fake-quant simulation share one quantizer):

* dense weights   — per-tensor affine int8 codes (``core.affine``),
* conv weights    — per-output-channel int8 codes, computed in int8 via
  tap-major int8 patches: the conv input is quantized once, its 3x3 taps
  are row shifts of the int8 codes concatenated along K in ``(kh, kw,
  C_in)`` order, and the patches go through the same W8A8 GEMM with the
  per-channel scales in the kernel's per-column dequant epilogue,
* activations     — dynamic per-tensor quantization at each dense/conv
  input (computed on the fly from the live batch range; no calibration).

Packing cadence: call ``pack_actor_params`` once per learner update — e.g.
at the top of a jitted training iteration — NOT per environment step; the
rollout scan then closes over the int8 cache.  ``rl.a2c`` / ``rl.dqn``
(``actor_backend="int8"``) and ``rl.distributed`` do exactly this.

Kernel backend selection (threaded through ``backend=`` everywhere):

    "pallas"     pallas_call, compiled       (TPU hot path)
    "interpret"  pallas_call, interpret mode (CPU kernel validation)
    "xla"        lax integer/centered GEMMs  (CPU/GPU hot path)
    "ref"        pure-jnp oracle             (CPU correctness / pjit)
    "auto"       pallas on TPU, xla elsewhere (default; see also the
                 ``REPRO_KERNEL_BACKEND`` env override in ``kernels.ops``)

Entry points:

* ``pack_actor_params(params, bits)``        -> int ``QuantizedParams``
  (``bits <= 4``: W4A8 — codes byte-packed two-per-byte, half the cache)
* ``calibrate_actor_cache(qparams, obs)``    -> cache + static activation
  scales; MLP applies then run the single-pass fused kernel
  (``kernels.fused_qmlp``) instead of one GEMM + dynamic range pass per
  layer
* ``make_actor_cache(params, backend, calib_obs=...)`` -> the one-stop
  pack(+calibrate) used at every cache-refresh site
* ``repack_restored_cache(cache, params, backend)`` -> a checkpointed
  cache in the packing order ``int8_conv2d`` reads
* ``quantized_apply(qparams, obs)``          -> head outputs (logits/q/mu)
* ``make_act_fn(env_spec)``                  -> deterministic deployment
  policy ``act(qparams, obs)`` (argmax for discrete, tanh*scale for DDPG)
* ``make_sampling_policy(env_spec, n_act)``  -> stochastic rollout policy
  ``policy(qparams, obs, key)`` for the training-time data-collection path.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.core import affine, ptq
from repro.core.ptq import PackedTensor
from repro.core.qconfig import QuantConfig
from repro.kernels import ops

# A QuantizedParams pytree mirrors the network spec: every weight leaf is a
# ``core.ptq.PackedTensor`` (int8 codes + affine scale/zero), biases stay f32.
# ``calibrate_actor_cache`` adds an ``ACT_QUANT`` entry of static activation
# scales next to the weights, which flips MLP applies onto the fused
# single-pass kernel.
QuantizedParams = Any

# The one place actor-backend strings are defined/validated — the configs,
# ``loops.train``, ``eval_policy``, ``launch.serve`` and the actor-learner
# topologies all route through ``validate_actor_backend``.
ACTOR_BACKENDS = ("fp32", "int8", "int4")
QUANTIZED_BACKENDS = ("int8", "int4")
_BACKEND_BITS = {"int8": 8, "int4": 4}

# key of the static activation-scale entry a calibrated cache carries
# (sorted next to the fc*/out weight entries in the packed pytree)
ACT_QUANT = "act_quant"


def validate_actor_backend(actor_backend: str) -> str:
    """Validate an actor-backend name against ``ACTOR_BACKENDS``.

    Returns the name unchanged (so it chains: ``bits =
    _BACKEND_BITS[validate_actor_backend(b)]``); raises ``ValueError``
    for anything outside ``("fp32", "int8", "int4")``.  Every config
    surface (``loops.train``, topologies, ``serving.PolicyServer``)
    funnels through here so the error reads the same everywhere.
    """
    if actor_backend not in ACTOR_BACKENDS:
        raise ValueError(f"actor_backend must be one of {ACTOR_BACKENDS}, "
                         f"got {actor_backend!r}")
    return actor_backend


def is_quantized(actor_backend: str) -> bool:
    """True for the integer-inference backends (int8/int4)."""
    return validate_actor_backend(actor_backend) in QUANTIZED_BACKENDS


def backend_bits(actor_backend: str) -> int:
    """Weight bit-width of a quantized actor backend (int8 -> 8, int4 -> 4)."""
    validate_actor_backend(actor_backend)
    if actor_backend not in _BACKEND_BITS:
        raise ValueError(f"actor_backend {actor_backend!r} is not a "
                         f"quantized backend {QUANTIZED_BACKENDS}")
    return _BACKEND_BITS[actor_backend]


def pack_actor_params(params: Any, bits: int = 8) -> QuantizedParams:
    """Pack an actor param pytree into the int-code deployment cache.

    Same quantizer as the fake-quant simulation (``ptq.ptq_simulate``):
    per-tensor for dense kernels, per-output-channel for conv kernels.
    Weight bits may be < 8 — ``bits <= 4`` stores two codes per int8 byte
    along the GEMM contraction axis (``actor_backend="int4"`` -> W4A8,
    half the int8 cache/sync footprint); activations always quantize to
    8 bits at run time (W{n}A8).  Jit-safe — call inside a training
    iteration to refresh the cache once per learner update.
    """
    # ValueError, not assert: the guard must survive ``python -O``
    if not 1 <= bits <= 8:
        raise ValueError(f"int actor cache needs 1 <= bits <= 8, "
                         f"got {bits}")
    return ptq.ptq_pack(params, QuantConfig.ptq_int(bits))


def packed_nbytes(qparams: QuantizedParams) -> int:
    """Parameter-memory footprint of the packed actor (paper's ~4x claim)."""
    return ptq.tree_nbytes(qparams)


def calib_slice(obs: jnp.ndarray, calib_batch: int) -> jnp.ndarray:
    """Leading-axis slice of a rollout observation batch for calibration."""
    return obs[:max(1, min(calib_batch, obs.shape[0]))]


def make_actor_cache(params: Any, actor_backend: str, *,
                     calib_obs: Any = None,
                     backend: str = "auto") -> QuantizedParams:
    """Pack (and, with ``calib_obs``, calibrate) one actor cache.

    The one-stop repack used at every cache refresh site — the fused
    drivers' per-update pack, the actor-learner ``lax.cond`` sync repack
    and the async snapshot program: codes at the backend's bit-width
    (int8 -> W8A8, int4 -> byte-packed W4A8), plus static activation
    scales (-> the single-pass fused MLP kernel) when a calibration
    observation batch is supplied.
    """
    qparams = pack_actor_params(params, backend_bits(actor_backend))
    if calib_obs is not None:
        qparams = calibrate_actor_cache(qparams, calib_obs, backend=backend)
    return qparams


def repack_restored_cache(cache: Any, params: Any, actor_backend: str
                          ) -> Any:
    """A checkpointed cache, with byte-packed conv codes re-minted.

    A checkpoint checks a packed leaf's shape and dtype, not its K order,
    and byte-packed (``bits <= 4``) conv codes were once stored
    channel-major ``(C_in*kh*kw, C_out)``.  Re-minting from ``params``,
    the params the cache was packed from, gives the tap-major order
    ``int8_conv2d`` reads, and the same codes for a cache already in it.
    Conv caches never calibrate, so the re-mint is the whole cache.  Its
    leaves are fresh buffers (a bias is otherwise the params' own), since
    the cache rides a state that the training chunk donates.  Every other
    cache is returned as restored.
    """
    if (cache == () or backend_bits(actor_backend) > 4
            or not any(name.startswith("conv") for name in cache)):
        return cache
    return jax.tree_util.tree_map(
        jnp.array, make_actor_cache(params, actor_backend))


# ---------------------------------------------------------------------------
# int8 layers
# ---------------------------------------------------------------------------

def _col_arrays(w: PackedTensor, n: int):
    """Kernel-layout per-column (N,) scale/zero of a packed weight.

    Packed at pack time (``ptq._pack_leaf``) and read straight off the
    cache; the broadcast fallback only serves hand-built ``PackedTensor``s
    from before the hoist.
    """
    if w.col_scale is not None:
        return w.col_scale, w.col_zero
    return (jnp.broadcast_to(
                jnp.asarray(w.delta, jnp.float32).reshape(-1), (n,)),
            jnp.broadcast_to(
                jnp.asarray(w.zero_point, jnp.float32).reshape(-1), (n,)))


def int8_dense(layer: Dict[str, Any], x: jnp.ndarray, *,
               backend: str = "auto", act: Callable = None) -> jnp.ndarray:
    """One dense layer through the W8A8 integer GEMM.

    ``layer`` is ``{"w": PackedTensor, "b": f32}``; ``x`` is f32 with
    arbitrary leading batch dims.  The activation is dynamically quantized
    per-tensor — always to 8 bits, whatever the weight bit-width (W{n}A8:
    the fake-quant protocol this path mirrors quantizes weights only, so
    activation error must not scale with the weight sweep) — the product
    accumulates in int32, and the affine dequant is fused in the kernel
    epilogue.  Sub-8-bit caches (``pack_actor_params(bits=4)``) hold
    byte-packed codes; the GEMM unpacks them in-kernel.
    """
    w: PackedTensor = layer["w"]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xq, xp = affine.quantize_to_int(x2, 8)
    n = (w.orig_shape[-1] if w.orig_shape is not None
         else w.codes.shape[-1])
    w_scale, w_zero = _col_arrays(w, n)
    y = ops.int8_matmul(xq, w.codes, xp.delta, xp.zero_point, w_scale,
                        w_zero, backend=backend,
                        w_bits=w.bits if w.bits <= 4 else 8)
    y = y + layer["b"]
    if act is not None:
        y = act(y)
    return y.reshape(lead + (n,))


def _conv_taps(xq: jnp.ndarray, zero: jnp.ndarray, hw, kernel_hw
               ) -> jnp.ndarray:
    """Tap-major GEMM operand of a stride-1 SAME conv over flat int8 rows.

    ``xq`` is ``(N*H*W, C)`` int8 codes, row ``n*H*W + h*W + w``.  Tap
    ``(i, j)`` reads the input at ``(h + dy, w + dx)``, ``(dy, dx) = (i -
    (kh-1)//2, j - (kw-1)//2)``: the rows shifted by ``dy*W + dx``, with
    ``zero`` (the code of 0.0) where the source lies outside the image.
    The taps are concatenated along K, so feature ``(i*kw + j)*C + c``
    matches HWIO weights reshaped to ``(kh*kw*C, C_out)``.
    """
    h, w = hw
    kh, kw = kernel_hw
    m = xq.shape[0]
    py, px = (kh - 1) // 2, (kw - 1) // 2
    lo, hi = py * w + px, (kh - 1 - py) * w + (kw - 1 - px)
    padded = jnp.pad(xq, ((lo, hi), (0, 0)), constant_values=zero)
    r = jnp.arange(m, dtype=jnp.int32)
    col, row = r % w, (r // w) % h
    taps = []
    for dy in range(-py, kh - py):
        for dx in range(-px, kw - px):
            shift = lo + dy * w + dx
            tap = padded[shift:shift + m]
            inside = []
            if dy:
                inside.append((row + dy >= 0) & (row + dy < h))
            if dx:
                inside.append((col + dx >= 0) & (col + dx < w))
            if inside:
                ok = functools.reduce(jnp.logical_and, inside)
                tap = jnp.where(ok[:, None], tap, zero)
            taps.append(tap)
    return jnp.concatenate(taps, axis=1)


def int8_conv2d(layer: Dict[str, Any], x: jnp.ndarray, hw,
                act: Callable = jax.nn.relu, *, backend: str = "auto"
                ) -> jnp.ndarray:
    """Conv on flat rows through the W8A8 integer GEMM.

    ``x`` is f32 ``(N*H*W, C_in)``, row ``n*H*W + h*W + w`` of images of
    ``hw = (H, W)``; the result is ``(N*H*W, C_out)`` in the same row
    order, which is the GEMM's own output layout, so convs chain without
    a reshape.  The input is dynamically quantized per-tensor once (same
    policy as ``int8_dense``; SAME zero padding adds only 0.0, which the
    range already holds), and the GEMM operand is built tap-major from
    the int8 codes (``_conv_taps``) against HWIO codes reshaped to
    ``(kh*kw*C_in, C_out)``.  The per-channel weight scales sit in the
    kernel's per-column affine epilogue.  Every network builds 3x3,
    stride-1, SAME convs (``rl.networks.cnn_spec``), the one geometry
    this runs; an unpacked fp32 kernel (e.g. a partially-packed tree)
    runs a plain ``lax.conv`` of it.
    """
    w = layer["w"]
    h, wd = hw
    if not isinstance(w, PackedTensor):
        img = x.reshape(-1, h, wd, x.shape[-1])
        y = jax.lax.conv_general_dilated(
            img, w.astype(x.dtype), window_strides=(1, 1),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y = y.reshape(-1, y.shape[-1]) + layer["b"].astype(x.dtype)
        return act(y) if act is not None else y
    kh, kw, c_in, c_out = (w.orig_shape if w.orig_shape is not None
                           else w.codes.shape)
    xq, xp = affine.quantize_to_int(x, 8)
    zero = affine.quantize_with_params(jnp.zeros((), x.dtype), xp)
    patches = _conv_taps(xq, zero, hw, (kh, kw))
    # sub-8-bit codes are byte-packed in this (kh*kw*C_in, C_out) layout
    # at pack time; the GEMM unpacks them in-kernel
    w2 = (w.codes if w.orig_shape is not None
          else w.codes.reshape(kh * kw * c_in, c_out))
    w_scale, w_zero = _col_arrays(w, c_out)
    y = ops.int8_matmul(patches, w2, xp.delta, xp.zero_point, w_scale,
                        w_zero, backend=backend,
                        w_bits=w.bits if w.bits <= 4 else 8)
    y = y + layer["b"].astype(y.dtype)
    if act is not None:
        y = act(y)
    return y


# ---------------------------------------------------------------------------
# Quantized network applies (mirror rl.networks.mlp_apply / cnn_apply)
# ---------------------------------------------------------------------------

def _mlp_layer_names(n_hidden: int):
    return [f"fc{i}" for i in range(n_hidden)] + ["out"]


def _fused_layers(qparams: QuantizedParams, n_hidden: int):
    """``(QMLPLayer, ...)`` for the single-pass kernel from a calibrated
    cache (weights + the ``ACT_QUANT`` static activation params)."""
    from repro.kernels.fused_qmlp import QMLPLayer
    act = qparams[ACT_QUANT]
    layers = []
    for i, name in enumerate(_mlp_layer_names(n_hidden)):
        w: PackedTensor = qparams[name]["w"]
        k = (w.orig_shape[0] if w.orig_shape is not None
             else w.codes.shape[0])
        n = (w.orig_shape[-1] if w.orig_shape is not None
             else w.codes.shape[-1])
        w_scale, w_zero = _col_arrays(w, n)
        x_delta, x_zero = act[i]
        layers.append(QMLPLayer(
            codes=w.codes, col_scale=w_scale, col_zero=w_zero,
            bias=qparams[name]["b"], x_delta=x_delta, x_zero=x_zero,
            bits=w.bits, k=k))
    return tuple(layers)


def quantized_mlp_apply(qparams: QuantizedParams, x: jnp.ndarray,
                        n_hidden: int, *, backend: str = "auto"
                        ) -> jnp.ndarray:
    """MLP head outputs from a packed cache.

    Fused-vs-per-layer selection: a *calibrated* cache (one carrying the
    ``ACT_QUANT`` static activation scales — see ``calibrate_actor_cache``)
    runs the whole forward in one pass (``kernels.ops.fused_qmlp``: one
    kernel dispatch, inter-layer activations int8-resident, no dynamic
    range passes); an uncalibrated cache falls back to the per-layer GEMM
    with dynamic per-tensor activation quantization.
    """
    if ACT_QUANT in qparams:
        return ops.fused_qmlp(x, _fused_layers(qparams, n_hidden),
                              backend=backend)
    for i in range(n_hidden):
        x = int8_dense(qparams[f"fc{i}"], x, backend=backend,
                       act=jax.nn.relu)
    return int8_dense(qparams["out"], x, backend=backend)


def quantized_cnn_apply(qparams: QuantizedParams, x: jnp.ndarray,
                        n_convs: int, *, backend: str = "auto"
                        ) -> jnp.ndarray:
    """CNN head outputs from a packed cache (per-layer int8 path).

    ``x`` is f32 ``(*batch, H, W, C)`` — the convs run on flat ``(rows,
    C)`` activations (``int8_conv2d``) and the last one's rows flatten to
    ``(batch, H*W*C)`` for the FC, the ``(h, w, c)`` order of
    ``rl.networks.cnn_apply``; the ``(*batch, head_dim)`` f32 result
    restores the leading batch dims.  Conv caches never calibrate, so
    this is always the per-layer dynamic-quantization path.
    """
    batch_shape = x.shape[:-3]
    h, w, c = x.shape[-3:]
    x = x.reshape(-1, c)
    for i in range(n_convs):
        x = int8_conv2d(qparams[f"conv{i}"], x, (h, w), backend=backend)
    x = x.reshape(-1, h * w * x.shape[-1])
    x = int8_dense(qparams["fc"], x, backend=backend, act=jax.nn.relu)
    y = int8_dense(qparams["out"], x, backend=backend)
    return y.reshape(batch_shape + y.shape[-1:])


def quantized_apply(qparams: QuantizedParams, x: jnp.ndarray, *,
                    backend: str = "auto") -> jnp.ndarray:
    """Head outputs of the packed actor (dispatches on the packed spec).

    The packed pytree carries the network structure (``rl.networks`` layer
    naming): ``conv*`` keys select the CNN backbone, an ``embed`` key the
    decoder-transformer sequence policy (windowed form —
    ``quantized_seq_apply``), otherwise the MLP (single-pass fused when
    the cache is calibrated — see ``quantized_mlp_apply``).
    """
    names = set(qparams)
    if "embed" in names:
        return quantized_seq_apply(qparams, x, backend=backend)
    n_convs = sum(1 for n in names if n.startswith("conv"))
    if n_convs:
        return quantized_cnn_apply(qparams, x, n_convs, backend=backend)
    n_hidden = sum(1 for n in names if n.startswith("fc"))
    return quantized_mlp_apply(qparams, x, n_hidden, backend=backend)


# ---------------------------------------------------------------------------
# Quantized sequence policy (mirror models.seq_policy.seq_apply)
# ---------------------------------------------------------------------------

def _n_blocks(qparams: QuantizedParams) -> int:
    return sum(1 for n in qparams if n.startswith("blk"))


def quantized_seq_apply(qparams: QuantizedParams, obs: jnp.ndarray, *,
                        backend: str = "auto") -> jnp.ndarray:
    """Windowed int8 forward of the packed decoder transformer.

    The stateless mirror of ``models.seq_policy.seq_apply``: every dense
    projection runs through the W{n}A8 GEMM (dynamic per-tensor activation
    quantization), while rms-norms, softmax-attention and residual adds
    stay fp32 on the activations.  ``obs`` is ``(..., context, feat)``
    frame-stacked rows with the trailing valid flag; output is the head on
    the newest row.  Used by eval / divergence / fp-comparison paths; the
    rollout hot path steps incrementally via ``quantized_seq_step``.
    """
    from repro.models import common as mcommon
    from repro.models.seq_policy import NEG_INF, valid_mask

    s = obs.shape[-2]
    x = int8_dense(qparams["embed"], obs, backend=backend)
    valid = valid_mask(obs)
    mask = jnp.tril(jnp.ones((s, s), bool)) & valid[..., None, :]
    scale = x.shape[-1] ** -0.5
    for i in range(_n_blocks(qparams)):
        blk = qparams[f"blk{i}"]
        h = mcommon.rms_norm(blk["ln1"], x)
        q = int8_dense(blk["q"], h, backend=backend)
        k = int8_dense(blk["k"], h, backend=backend)
        v = int8_dense(blk["v"], h, backend=backend)
        logits = jnp.einsum("...sd,...td->...st", q, k) * scale
        logits = jnp.where(mask, logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        a = jnp.einsum("...st,...td->...sd", p, v)
        x = x + int8_dense(blk["o"], a, backend=backend)
        h2 = mcommon.rms_norm(blk["ln2"], x)
        y = int8_dense(blk["fc"], h2, backend=backend, act=jax.nn.relu)
        x = x + int8_dense(blk["proj"], y, backend=backend)
    return int8_dense(qparams["head"], x[..., -1, :], backend=backend)


def seq_cache_zeros(seq_cfg, n_envs: int, size: int) -> Dict[str, Any]:
    """All-zero per-env KV-cache actor state for the sequence policy.

    One plain-layout (slot == step index) int8 cache per block: codes
    ``(n_envs, size, d_model)`` with per-token scales, plus the per-env
    write counter.  ``size`` must exceed the longest episode (the drivers
    use ``env.spec.max_steps + 1``); the all-zero tree is also the
    per-env reset value ``auto_reset_step`` restores on episode end (see
    ``rl.env.attach_policy_state``).
    """
    def layer():
        return {
            "k_codes": jnp.zeros((n_envs, size, seq_cfg.d_model), jnp.int8),
            "k_scale": jnp.zeros((n_envs, size, 1), jnp.float32),
            "v_codes": jnp.zeros((n_envs, size, seq_cfg.d_model), jnp.int8),
            "v_scale": jnp.zeros((n_envs, size, 1), jnp.float32),
        }
    return {"count": jnp.zeros((n_envs,), jnp.int32),
            "layers": tuple(layer() for _ in range(seq_cfg.n_layers))}


def seq_cache_nbytes(pstate: Dict[str, Any]) -> int:
    """Total bytes of a KV-cache actor state (codes + scales + counter)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(pstate))


def quantized_seq_step(qparams: QuantizedParams, feat: jnp.ndarray,
                       pstate: Dict[str, Any], *, context: int,
                       backend: str = "auto"):
    """One decode step of the packed transformer on the int8 KV cache.

    ``feat`` is the newest frame row ``(B, feat)``; ``pstate`` the
    per-env cache from ``seq_cache_zeros``.  Each block quantizes the new
    token's K/V with the shared ``core.affine.quantize_symmetric``,
    writes slot ``count``, and attends over the last ``context`` slots
    through ``kernels.ops.int8_cache_attention`` — so the token set (and
    the fp32 attention math over dequantized codes) matches the windowed
    ``quantized_seq_apply`` on the corresponding frame stack; the two
    differ only by activation-quantization batching (documented tolerance
    — docs/contracts.md "Attention parity").  Returns ``(head_out,
    new_pstate)`` with ``count`` advanced.
    """
    from repro.models import common as mcommon

    count = pstate["count"]
    x = int8_dense(qparams["embed"], feat, backend=backend)      # (B, D)

    def write(buf, val, c):
        return jax.vmap(
            lambda b, v, i: jax.lax.dynamic_update_slice(b, v[None],
                                                         (i, 0))
        )(buf, val, c)

    new_layers = []
    for i in range(_n_blocks(qparams)):
        blk = qparams[f"blk{i}"]
        cache = pstate["layers"][i]
        h = mcommon.rms_norm(blk["ln1"], x)
        q = int8_dense(blk["q"], h, backend=backend)
        k = int8_dense(blk["k"], h, backend=backend)
        v = int8_dense(blk["v"], h, backend=backend)
        kc, ks = affine.quantize_symmetric(k)
        vc, vs = affine.quantize_symmetric(v)
        cache = {"k_codes": write(cache["k_codes"], kc, count),
                 "k_scale": write(cache["k_scale"], ks, count),
                 "v_codes": write(cache["v_codes"], vc, count),
                 "v_scale": write(cache["v_scale"], vs, count)}
        out = ops.int8_cache_attention(
            q[:, None, :], cache["k_codes"], cache["k_scale"],
            cache["v_codes"], cache["v_scale"], count, window=context,
            backend=backend)
        x = x + int8_dense(blk["o"], out[:, 0, :], backend=backend)
        h2 = mcommon.rms_norm(blk["ln2"], x)
        y = int8_dense(blk["fc"], h2, backend=backend, act=jax.nn.relu)
        x = x + int8_dense(blk["proj"], y, backend=backend)
        new_layers.append(cache)
    head = int8_dense(qparams["head"], x, backend=backend)
    return head, {"count": count + 1, "layers": tuple(new_layers)}


def maybe_attach_seq_state(benv, net, actor_backend: str, n_envs: int):
    """Wrap a batched env with KV-cache actor state when it applies.

    No-op unless ``net`` carries a ``seq_cfg`` AND the actor backend is
    quantized — exactly the condition under which the rollout policy is
    the stateful cached stepper (``quantized_seq_step``); fp32 sequence
    actors stay stateless-windowed.  The wrapped state rides through
    rollout scans, shard_map partitioning (batch-leading leaves) and the
    checkpoint/resume contract as ordinary env state.
    """
    seq_cfg = getattr(net, "seq_cfg", None)
    if seq_cfg is None or not is_quantized(actor_backend):
        return benv
    from repro.rl.env import attach_policy_state
    pstate0 = seq_cache_zeros(seq_cfg, n_envs, benv.spec.max_steps + 1)
    return attach_policy_state(benv, pstate0)


def calibrate_actor_cache(qparams: QuantizedParams, obs: jnp.ndarray, *,
                          backend: str = "auto") -> QuantizedParams:
    """Attach static activation scales to a packed MLP cache.

    Runs the per-layer dynamic path once over ``obs`` (a replay/rollout
    observation batch) and records, per dense layer, the affine params the
    dynamic quantizer derives for that layer's input — exactly the values
    ``int8_dense`` would compute on this batch, which is the fused kernel's
    bitwise-anchor contract.  The params come back cached in the packed
    pytree under ``ACT_QUANT`` (next to the weights, so the cache rides
    sync/snapshot transfers as one pytree) and ``quantized_apply`` then
    takes the single-pass fused kernel: no per-layer dynamic min/max
    reduction, inter-layer activations int8-resident.

    Call once per sync — the actor-learner topologies refresh it inside
    the PR-4 ``lax.cond`` repack / snapshot programs (``calib_batch`` on
    the configs).  CNN caches pass through uncalibrated (the fused kernel
    is MLP-only; conv actors keep the per-layer path).
    """
    names = set(qparams)
    if "embed" in names or any(n.startswith("conv") for n in names):
        # the fused kernel is MLP-only: transformer and conv caches keep
        # the per-layer dynamic-quantization path, calibration is a no-op
        return qparams
    n_hidden = sum(1 for n in names if n.startswith("fc"))
    act = []
    x = obs.reshape(-1, obs.shape[-1]).astype(jnp.float32)
    for i, name in enumerate(_mlp_layer_names(n_hidden)):
        p = affine.calibration_params(x, 8)
        act.append((p.delta, p.zero_point))
        if i < n_hidden:
            x = int8_dense(qparams[name], x, backend=backend,
                           act=jax.nn.relu)
    return {**qparams, ACT_QUANT: tuple(act)}


# ---------------------------------------------------------------------------
# Policy heads
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_act_fn(env_spec, *, backend: str = "auto"):
    """Deterministic deployment policy over packed params.

    Signature matches ``rl.env.evaluate``'s ``act_fn(params, obs)`` with the
    packed pytree in the params slot: discrete envs argmax over the first
    ``n_actions`` head outputs (A2C/PPO value heads are sliced off, DQN maps
    through unchanged); continuous envs apply the DDPG tanh*scale head.

    Cached per ``(env_spec, backend)`` (``EnvSpec`` is frozen/hashable) so
    repeated deployments of one env share an act-fn identity — which is
    what lets ``rl.env.evaluate`` reuse its compiled eval program.
    """
    if env_spec.continuous:
        def act(qparams, obs):
            """Continuous head: tanh * action_scale, f32 actions."""
            mu = quantized_apply(qparams, obs, backend=backend)
            return jnp.tanh(mu) * env_spec.action_scale
    else:
        n_act = env_spec.n_actions

        def act(qparams, obs):
            """Discrete head: argmax over n_actions logits, int32."""
            out = quantized_apply(qparams, obs, backend=backend)
            return jnp.argmax(out[..., :n_act], axis=-1).astype(jnp.int32)
    return act


@functools.lru_cache(maxsize=None)
def make_sampling_policy(env_spec, *, backend: str = "auto"):
    """Stochastic rollout policy (training-time data collection).

    Returns ``policy(qparams, obs, key) -> (action, logits)`` sampling from
    the int8 actor's categorical head — the ActorQ data-collection path.
    """
    n_act = env_spec.n_actions

    def policy(qparams, obs, key):
        """Sample an int32 action from the categorical head; keep logits."""
        out = quantized_apply(qparams, obs, backend=backend)
        logits = out[..., :n_act]
        return jax.random.categorical(key, logits).astype(jnp.int32), logits
    return policy
