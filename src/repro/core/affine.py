"""Paper-faithful uniform affine quantization (QuaRL Sec. 3.1).

The paper defines, for an n-bit quantizer over a tensor W:

    delta = (|min(W, 0)| + |max(W, 0)|) / 2**n
    z     = round(-min(W, 0) / delta)
    Q(W)  = round(W / delta) + z
    D(q)  = delta * (q - z)

``min(W,0)``/``max(W,0)`` extend the range to always include zero so that zero
is exactly representable (required so that e.g. zero-padding and ReLU zeros are
exact). Quantized codes live in [0, 2**n - 1].

Per-tensor quantization is used for fully connected layers; per-axis
(output-channel) quantization for convolutions — both per the paper.

Faithfulness note: the paper divides the range by 2**n (not 2**n - 1), so the
top of the range maps to code 2**n, which clips to 2**n - 1 — edge values can
lose up to ~1.5*delta (vs 0.5*delta interior). We reproduce this exactly; the
property tests encode the 1.5*delta bound.

Everything here is pure jnp so it can serve as the oracle for the Pallas
kernels in ``repro.kernels`` and be fused inside jitted training steps.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


class AffineParams(NamedTuple):
    """Quantizer parameters. ``delta`` and ``zero_point`` broadcast against W."""
    delta: jnp.ndarray       # step size (>0)
    zero_point: jnp.ndarray  # integer offset (stored as float for jax friendliness)
    bits: int


def _order_keys(i: jnp.ndarray) -> jnp.ndarray:
    """Self-inverse int32 transform of f32 bit patterns whose int ordering
    matches the float ordering (flip the magnitude bits of negatives)."""
    return i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))


def _range_including_zero(w: jnp.ndarray, axes: Optional[Sequence[int]]
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(min(W,0), max(W,0)) reduced over ``axes`` (None = all axes)."""
    keep = axes is not None
    if w.dtype == jnp.float32 and jax.default_backend() == "cpu":
        # XLA:CPU lowers float min/max reductions to a slow scalar loop
        # (~7x its integer reductions — this range pass dominated the
        # dynamic-quantization cost of the int8 actor hot path), so reduce
        # order-isomorphic int32 keys instead.  Exact for every finite
        # float: only the sign of a -0.0/0.0 tie and NaN propagation can
        # differ, neither of which changes the derived affine params.
        keys = _order_keys(jax.lax.bitcast_convert_type(w, jnp.int32))
        wmin = jax.lax.bitcast_convert_type(
            _order_keys(jnp.min(keys, axis=axes, keepdims=keep)),
            jnp.float32)
        wmax = jax.lax.bitcast_convert_type(
            _order_keys(jnp.max(keys, axis=axes, keepdims=keep)),
            jnp.float32)
    else:
        wmin = jnp.min(w, axis=axes, keepdims=keep)
        wmax = jnp.max(w, axis=axes, keepdims=keep)
    return jnp.minimum(wmin, 0.0), jnp.maximum(wmax, 0.0)


def affine_params_from_range(wmin: jnp.ndarray, wmax: jnp.ndarray,
                             bits: int) -> AffineParams:
    """Paper's delta/z from a (min,max) range. Range is first extended to 0."""
    wmin = jnp.minimum(wmin, 0.0)
    wmax = jnp.maximum(wmax, 0.0)
    n_levels = 2.0 ** bits
    delta = (jnp.abs(wmin) + jnp.abs(wmax)) / n_levels
    # Degenerate all-zero tensor: delta == 0. Use 1.0 so Q(0)=z, D(z)=0 exactly.
    delta = jnp.where(delta == 0.0, 1.0, delta)
    zero_point = jnp.round(-wmin / delta)
    return AffineParams(delta=delta, zero_point=zero_point, bits=bits)


def compute_affine_params(w: jnp.ndarray, bits: int,
                          axis: Optional[int] = None) -> AffineParams:
    """Per-tensor (axis=None) or per-axis (quantization axis kept) params."""
    if axis is None:
        wmin, wmax = _range_including_zero(w, None)
    else:
        axis = axis % w.ndim
        reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
        wmin, wmax = _range_including_zero(w, reduce_axes)
    return affine_params_from_range(wmin, wmax, bits)


def quantize(w: jnp.ndarray, params: AffineParams) -> jnp.ndarray:
    """W -> integer codes in [0, 2**bits - 1] (returned as float dtype of W)."""
    q = jnp.round(w / params.delta) + params.zero_point
    return jnp.clip(q, 0.0, 2.0 ** params.bits - 1.0)


def dequantize(q: jnp.ndarray, params: AffineParams) -> jnp.ndarray:
    return params.delta * (q - params.zero_point)


def quantize_dequantize(w: jnp.ndarray, params: AffineParams) -> jnp.ndarray:
    """The paper's Q followed by D — the "fake quantization" value map."""
    return dequantize(quantize(w, params), params)


def ptq_tensor(w: jnp.ndarray, bits: int, axis: Optional[int] = None
               ) -> jnp.ndarray:
    """One-shot post-training quantize-dequantize of a tensor (Algorithm 1)."""
    return quantize_dequantize(w, compute_affine_params(w, bits, axis))


def quantize_to_int(w: jnp.ndarray, bits: int, axis: Optional[int] = None
                    ) -> Tuple[jnp.ndarray, AffineParams]:
    """Quantize and pack into the narrowest integer dtype (deployment path)."""
    params = compute_affine_params(w, bits, axis)
    q = quantize(w, params)
    dtype = jnp.int8 if bits <= 8 else jnp.int16
    # int8 holds [0,255]? No — shift to signed storage: store q - 2**(bits-1).
    offset = 2.0 ** (bits - 1)
    q_signed = (q - offset).astype(dtype)
    shifted = AffineParams(delta=params.delta,
                           zero_point=params.zero_point - offset,
                           bits=bits)
    return q_signed, shifted


def dequantize_from_int(q: jnp.ndarray, params: AffineParams,
                        dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    return (params.delta * (q.astype(dtype) - params.zero_point)).astype(dtype)


def quantize_with_params(w: jnp.ndarray, params: AffineParams
                         ) -> jnp.ndarray:
    """Quantize with *precomputed* signed-storage params (static requant).

    ``params`` must be the shifted form produced by ``quantize_to_int`` /
    ``calibration_params`` (zero_point offset by ``-2**(bits-1)`` so codes
    store signed).  With params computed from the same tensor this is
    bit-identical to ``quantize_to_int(w, bits)[0]`` — the contract behind
    the fused kernel's static-requant anchor: clip(round(w/delta) + z, 0,
    2**b - 1) - 2**(b-1) == clip(round(w/delta) + (z - 2**(b-1)),
    -2**(b-1), 2**(b-1) - 1).
    """
    half = 2.0 ** (params.bits - 1)
    q = jnp.round(w / params.delta) + params.zero_point
    dtype = jnp.int8 if params.bits <= 8 else jnp.int16
    return jnp.clip(q, -half, half - 1.0).astype(dtype)


def calibration_params(w: jnp.ndarray, bits: int = 8) -> AffineParams:
    """Signed-storage activation params from a calibration batch.

    The static-requant helper behind the fused actor kernel: the affine
    params ``quantize_to_int`` would derive from ``w`` (paper formula,
    range extended to zero) in the shifted signed form, WITHOUT quantizing
    — cache these once per sync, then ``quantize_with_params`` replaces the
    per-call dynamic min/max pass.
    """
    params = compute_affine_params(w, bits, axis=None)
    offset = 2.0 ** (bits - 1)
    return AffineParams(delta=params.delta,
                        zero_point=params.zero_point - offset, bits=bits)


# ---------------------------------------------------------------------------
# Sub-8-bit storage: two int4 codes per int8 byte
# ---------------------------------------------------------------------------

def pack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """Pack signed int4 codes (values in [-8, 7], stored int8) two per byte.

    Packs along axis 0 (the GEMM contraction axis) by halves: with
    ``h = ceil(K/2)``, byte row ``i`` holds code row ``i`` in its low
    nibble and code row ``h + i`` in its high nibble —
    ``(K, N) -> (h, N)``.  An odd K zero-pads the last high nibble.  Each
    nibble plane is a contiguous K-slice, so a GEMM contracts ``x[:, :h]``
    against the low nibbles and ``x[:, h:]`` against the high ones with no
    interleaving reshape (``int4_halves``).
    """
    k = codes.shape[0]
    h = (k + 1) // 2
    if k % 2:
        pad = [(0, 1)] + [(0, 0)] * (codes.ndim - 1)
        codes = jnp.pad(codes, pad)
    lo = codes[:h].astype(jnp.uint8) & 0xF
    hi = codes[h:].astype(jnp.uint8) & 0xF
    # same-width bitcast, not a value convert: 0x80..0xFF must become the
    # negative byte patterns, which int astype leaves implementation-defined
    return (lo | (hi << 4)).view(jnp.int8)


def int4_halves(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(low, high)`` nibble planes of ``pack_int4`` bytes as int32 codes.

    Sign-extends each nibble with a left-then-arithmetic-right shift pair
    on int32 — the TPU compiler has no shifts on int8 vectors, so this is
    the form the in-kernel unpack of the W4A8 GEMMs uses, and the one the
    oracles use too (identical codes on every backend).
    """
    v = packed.astype(jnp.int32)
    return (v << 28) >> 28, (v << 24) >> 28


def unpack_int4(packed: jnp.ndarray, k: int) -> jnp.ndarray:
    """Inverse of ``pack_int4``: ``(ceil(K/2), N) -> (K, N)`` int8 codes."""
    lo, hi = int4_halves(packed)
    return jnp.concatenate([lo, hi], axis=0)[:k].astype(jnp.int8)


def quantize_symmetric(x: jnp.ndarray, axis: int = -1
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-slice int8 quantization (the KV-cache token quantizer).

    Reduces ``|x|`` over ``axis`` (keepdims) and maps the slice onto
    [-127, 127] with ``scale = amax / 127`` (an all-zero slice gets scale 1
    so its codes are exactly zero).  Returns ``(codes int8, scale f32)``
    with ``scale`` broadcastable against ``x``; dequantization is
    ``codes * scale``.

    This is the *symmetric* (zero-point-free) companion to the affine
    scheme above — attention caches quantize per token where a zero-point
    correction would put an extra (T,)-shaped term inside the attention
    kernel for no range benefit (K/V activations are roughly centered).
    It is the single source of truth for KV-cache codes:
    ``models.attention.cache_update`` and the ActorQ sequence actors
    (``rl.actorq``) both call it, and the regression test
    ``tests/test_seq_policy.py::test_symmetric_quantizer_matches_legacy``
    pins it bitwise to the formula ``models/attention.py`` used before the
    merge (amax/127 scale, round, clip to [-127, 127]).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                     ).astype(jnp.int8)
    return codes, scale


def fp16_quantize(w: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 fp16 round-trip (paper's Q_fp16)."""
    return w.astype(jnp.float16).astype(w.dtype)


def quantization_error(w: jnp.ndarray, bits: int,
                       axis: Optional[int] = None) -> jnp.ndarray:
    """Mean absolute quantization error — used by the weight-distribution study."""
    return jnp.mean(jnp.abs(w - ptq_tensor(w, bits, axis)))
