"""Pallas TPU kernel: the whole quantized-MLP actor forward in ONE pass.

The per-layer ActorQ hot path (``rl.actorq.quantized_mlp_apply``) pays, for
every dense layer: one GEMM kernel dispatch, an fp32 activation round trip
through HBM, a full dynamic min/max reduction over that activation, and a
re-quantize before the next GEMM.  This kernel runs the *entire* MLP forward
— every layer's W8A8 (or W4A8) GEMM with int32 accumulation — inside one
``pallas_call``:

* the grid iterates over batch-row blocks only; every layer's weight block
  is resident in VMEM for the whole pass,
* each hidden layer ends in a fused bias + ReLU + **requantize-to-int8**
  epilogue using *static* activation scales (``QMLPLayer.x_delta`` /
  ``x_zero``, calibrated once per sync — ``core.affine.calibration_params``)
  so inter-layer activations stay int8 in VMEM and never touch fp32 HBM,
* only the head layer dequantizes, writing the fp32 logits/q/mu output.

Sub-8-bit weights (``bits <= 4``) are stored two int4 codes per int8 byte
along the contraction axis (``core.affine.pack_int4``) and unpacked
in-kernel — W4A8: half the actor-cache bytes, same A8 activation protocol.

The float epilogue mirrors ``ref.int8_matmul_ref`` op for op (scale product,
then correction multiply, then bias add), so with static scales equal to the
dynamic ones the fused path is *bitwise* identical to the per-layer path —
the anchor contract tested in ``tests/test_fused_qmlp.py``.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import affine


class QMLPLayer(NamedTuple):
    """One fused-MLP layer: kernel-layout weights + static input quant.

    ``codes`` is ``(K, N)`` int8, or ``(ceil(K/2), N)`` packed pairs when
    ``bits <= 4``; ``col_scale``/``col_zero`` are the per-column dequant
    arrays hoisted at pack time; ``x_delta``/``x_zero`` are the *static*
    affine params (signed-storage form) of this layer's input activation —
    layer 0's pair quantizes the observation, layer ``i+1``'s pair is the
    requant target of hidden layer ``i``'s epilogue.

    ``bits`` and ``k`` (the true contraction length) are static pytree aux
    so jitted callers re-trace on structure, not on values.
    """
    codes: jnp.ndarray
    col_scale: jnp.ndarray    # (N,) f32
    col_zero: jnp.ndarray     # (N,) f32
    bias: jnp.ndarray         # (N,) f32
    x_delta: jnp.ndarray      # () f32 static input-activation scale
    x_zero: jnp.ndarray       # () f32 signed-storage zero point
    bits: int = 8
    k: int = 0


jax.tree_util.register_pytree_node(
    QMLPLayer,
    lambda p: ((p.codes, p.col_scale, p.col_zero, p.bias, p.x_delta,
                p.x_zero), (p.bits, p.k)),
    lambda aux, xs: QMLPLayer(*xs, aux[0], aux[1]))


def _layer_forward(h: jnp.ndarray, w: jnp.ndarray, col_scale, col_zero,
                   bias, x_delta, x_zero, bits: int, k: int) -> jnp.ndarray:
    """int8 GEMM + zero-point correction + dequant epilogue for one layer.

    ``h`` is (bm, k) int8 codes; ``w`` the layer's (k, n) int8 codes, or
    the (ceil(k/2), n) ``pack_int4`` bytes when ``bits <= 4`` — each
    nibble plane then contracts against its own contiguous K half of
    ``h``.  Returns the fp32 (bm, n) pre-activation.  Float op order
    matches ``ref.int8_matmul_ref`` exactly (the bitwise-anchor contract).
    """
    if bits <= 4:
        kh = (k + 1) // 2
        lo, hi = affine.int4_halves(w)
        parts = [(h[:, :kh], lo)]
        if k > kh:
            parts.append((h[:, kh:], hi[:k - kh]))
    else:
        parts = [(h, w)]
    acc = sum_h = sum_w = 0
    for hp, wp in parts:
        # int8 codes straight into the MXU, int32 accumulation
        acc = acc + jax.lax.dot_general(
            hp, wp.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        sum_h = sum_h + jnp.sum(hp.astype(jnp.int32), axis=1,
                                keepdims=True)           # (bm, 1)
        sum_w = sum_w + jnp.sum(wp.astype(jnp.int32), axis=0,
                                keepdims=True)           # (1, n)
    xz = x_zero.astype(jnp.int32)
    wz = col_zero.astype(jnp.int32)                      # (1, n)
    corr = acc - xz * sum_w - wz * sum_h + k * xz * wz
    y = x_delta * col_scale * corr.astype(jnp.float32)
    return y + bias


def _fused_qmlp_kernel(*refs, metas: Tuple[Tuple[int, int], ...]):
    """``refs`` = x, then 6 refs per layer (codes, col_scale, col_zero,
    bias, x_delta, x_zero), then the output; ``metas`` = static
    ``(bits, k)`` per layer."""
    x_ref, o_ref = refs[0], refs[-1]
    h = x_ref[...]
    n_layers = len(metas)
    for i, (bits, k) in enumerate(metas):
        c_ref, ws_ref, wz_ref, b_ref, xd_ref, xz_ref = refs[1 + 6 * i:
                                                            7 + 6 * i]
        y = _layer_forward(h, c_ref[...], ws_ref[0, :][None, :],
                           wz_ref[0, :][None, :], b_ref[0, :][None, :],
                           xd_ref[0, 0], xz_ref[0, 0], bits, k)
        if i + 1 < n_layers:
            # fused epilogue: ReLU + static requant — the activation stays
            # int8-coded in VMEM for the next MXU feed
            y = jnp.maximum(y, 0.0)
            nxd_ref, nxz_ref = refs[1 + 6 * (i + 1) + 4:1 + 6 * (i + 1) + 6]
            q = jnp.round(y / nxd_ref[0, 0]) + nxz_ref[0, 0]
            h = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)
        else:
            o_ref[...] = y.astype(o_ref.dtype)


def fused_qmlp_pallas(x_q: jnp.ndarray, layers: Tuple[QMLPLayer, ...], *,
                      block_m: int = 256, out_dtype: Any = jnp.float32,
                      interpret: bool = False) -> jnp.ndarray:
    """Single-pass MLP forward over int8 input codes.

    ``x_q`` is ``(M, K0)`` int8, already quantized with layer 0's static
    params (``kernels.ops.fused_qmlp`` does this).  The grid blocks M only;
    all weights ride as full-array VMEM blocks (actor MLPs are Table-5
    sized — a 3x256 policy is ~200KB packed, far under the VMEM budget).
    Rows past M in the final block compute on padding and are discarded by
    the output masking pallas applies.
    """
    m, k0 = x_q.shape
    if not layers:
        raise ValueError("fused_qmlp needs at least one layer")
    if layers[0].k != k0:
        raise ValueError(f"layer 0 expects K={layers[0].k}, x has {k0}")
    n_out = layers[-1].codes.shape[-1]
    bm = min(block_m, m)
    grid = (pl.cdiv(m, bm),)

    operands = [x_q]
    in_specs = [pl.BlockSpec((bm, k0), lambda i: (i, 0))]
    metas = []
    for layer in layers:
        metas.append((layer.bits, layer.k))
        n = layer.codes.shape[-1]
        full = layer.codes.shape
        for arr, spec in (
                (layer.codes, pl.BlockSpec(full, lambda i: (0, 0))),
                (layer.col_scale.reshape(1, n),
                 pl.BlockSpec((1, n), lambda i: (0, 0))),
                (layer.col_zero.reshape(1, n),
                 pl.BlockSpec((1, n), lambda i: (0, 0))),
                (layer.bias.reshape(1, n).astype(jnp.float32),
                 pl.BlockSpec((1, n), lambda i: (0, 0))),
                (jnp.asarray(layer.x_delta, jnp.float32).reshape(1, 1),
                 pl.BlockSpec((1, 1), lambda i: (0, 0))),
                (jnp.asarray(layer.x_zero, jnp.float32).reshape(1, 1),
                 pl.BlockSpec((1, 1), lambda i: (0, 0)))):
            operands.append(arr)
            in_specs.append(spec)

    return pl.pallas_call(
        functools.partial(_fused_qmlp_kernel, metas=tuple(metas)),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype),
        interpret=interpret,
    )(*operands)
