"""Post-training quantization over parameter pytrees (QuaRL Algorithm 1).

Two forms are provided:

* ``ptq_simulate(params, config)`` — quantize-dequantize every weight matrix in
  place (values change, dtypes don't). This is what the paper evaluates: the
  policy is run in float math on quantization-error-injected weights.
* ``ptq_pack(params, config)`` / ``ptq_unpack`` — the deployment form: weights
  stored as int8 (+ per-tensor/per-axis scales), 4x smaller than fp32. The
  int8 matmul kernel in ``repro.kernels`` consumes these directly.

Which leaves quantize: any float array with ndim >= 2 is treated as a weight
(dense kernels, conv kernels, embeddings); biases/norm scales (ndim <= 1) stay
full precision, matching the paper's per-layer weight quantization. Conv
kernels (ndim == 4) get per-axis quantization over the output-channel axis.
A ``predicate(path, leaf)`` hook lets callers exclude e.g. MoE routers.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import affine
from repro.core.qconfig import QuantConfig, QuantMode

PyTree = Any
Predicate = Callable[[Tuple[Any, ...], jnp.ndarray], bool]


def _is_weight(path: Tuple[Any, ...], leaf: Any) -> bool:
    return (isinstance(leaf, (jnp.ndarray, jax.Array))
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.ndim >= 2)


def _axis_for(leaf: jnp.ndarray, config: QuantConfig) -> Optional[int]:
    # Per-axis (output-channel) quantization for conv kernels (HWIO -> axis -1),
    # per-tensor for everything else, per the paper.
    if config.per_axis_conv and leaf.ndim == 4:
        return leaf.ndim - 1
    return None


def ptq_simulate(params: PyTree, config: QuantConfig,
                 predicate: Predicate = _is_weight) -> PyTree:
    """Quantize-dequantize all weights (Algorithm 1's Q applied to M)."""
    if not config.is_ptq:
        return params

    def one(path, leaf):
        if not predicate(path, leaf):
            return leaf
        if config.mode == QuantMode.PTQ_FP16:
            return affine.fp16_quantize(leaf)
        return affine.ptq_tensor(leaf, config.bits, _axis_for(leaf, config))

    return jax.tree_util.tree_map_with_path(one, params)


class PackedTensor(NamedTuple):
    """An int-packed weight: codes + affine params (deployment format).

    ``col_scale`` / ``col_zero`` are the kernel-layout per-column ``(N,)``
    f32 dequant arrays the W8A8 GEMM epilogue consumes, materialized once
    at pack time (a per-tensor dense scale broadcasts, a per-channel conv
    scale flattens) instead of being rebuilt on every forward call.

    Sub-8-bit weights (``bits <= 4``) store ``codes`` *packed*: two int4
    codes per int8 byte along the GEMM contraction axis, already in the
    kernel's ``(K, N)`` layout (conv kernels are HWIO reshaped to the
    tap-major ``(kh*kw*C_in, C_out)`` feature order of
    ``rl.actorq.int8_conv2d``; a checkpoint does not record that order,
    so a restored conv cache is re-minted,
    ``rl.actorq.repack_restored_cache``).  ``orig_shape``
    carries the unpacked weight shape; ``None`` means codes are stored in
    the weight's natural layout (the int8 path).
    """
    codes: jnp.ndarray        # int8/int16; packed pairs when bits <= 4
    delta: jnp.ndarray
    zero_point: jnp.ndarray
    bits: int
    col_scale: Any = None     # (N,) f32 kernel-layout per-column scale
    col_zero: Any = None      # (N,) f32 kernel-layout per-column zero
    orig_shape: Any = None    # unpacked shape when codes are sub-8-bit

    def unpacked_codes(self) -> jnp.ndarray:
        """Codes widened to one-per-int8 in the stored layout."""
        if self.orig_shape is None:
            return self.codes
        k = 1
        for d in self.orig_shape[:-1]:
            k *= d
        return affine.unpack_int4(self.codes, k)

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        p = affine.AffineParams(self.delta, self.zero_point, self.bits)
        codes = self.unpacked_codes()
        if self.orig_shape is not None:
            # packed codes are the weight reshaped to (K, N) (a conv's HWIO
            # to (kh*kw*C_in, C_out)); restore it so delta/zero_point
            # broadcast as at pack time
            codes = codes.reshape(self.orig_shape)
        return affine.dequantize_from_int(codes, p, dtype)

    @property
    def nbytes(self) -> int:
        # col_scale/col_zero are *derived* broadcasts of delta/zero_point
        # (hoisted to pack time for the kernel epilogue) — not counted, so
        # the footprint metric stays about the quantizer payload: codes +
        # canonical affine params (the paper's ~4x claim; exactly-halved
        # codes under int4).
        return (self.codes.size * self.codes.dtype.itemsize
                + self.delta.size * 4 + self.zero_point.size * 4)


jax.tree_util.register_pytree_node(
    PackedTensor,
    lambda p: ((p.codes, p.delta, p.zero_point, p.col_scale, p.col_zero),
               (p.bits, p.orig_shape)),
    lambda aux, xs: PackedTensor(xs[0], xs[1], xs[2], aux[0], xs[3], xs[4],
                                 aux[1]))


def _pack_leaf(leaf: jnp.ndarray, bits: int,
               axis: Optional[int]) -> PackedTensor:
    """Quantize one weight into the kernel-ready PackedTensor layout."""
    codes, p = affine.quantize_to_int(leaf, bits, axis)
    n = leaf.shape[-1]
    col_scale = jnp.broadcast_to(
        jnp.asarray(p.delta, jnp.float32).reshape(-1), (n,))
    col_zero = jnp.broadcast_to(
        jnp.asarray(p.zero_point, jnp.float32).reshape(-1), (n,))
    # jnp.broadcast_to returns a view under tracing; commit real buffers so
    # the cache is self-contained when carried across program boundaries
    col_scale, col_zero = jnp.array(col_scale), jnp.array(col_zero)
    if bits > 4:
        return PackedTensor(codes, p.delta, p.zero_point, bits,
                            col_scale, col_zero)
    # sub-8-bit: reshape to the GEMM contraction layout (a conv's HWIO to
    # tap-major (kh*kw*C_in, C_out)) and pack two codes per byte along K
    # (see PackedTensor docstring)
    return PackedTensor(affine.pack_int4(codes.reshape(-1, n)), p.delta,
                        p.zero_point, bits, col_scale, col_zero,
                        orig_shape=tuple(leaf.shape))


def ptq_pack(params: PyTree, config: QuantConfig,
             predicate: Predicate = _is_weight) -> PyTree:
    """Pack weights into int storage; non-weights pass through unchanged."""
    if config.mode != QuantMode.PTQ_INT:
        raise ValueError(f"packing is for int PTQ, got {config.mode}")

    def one(path, leaf):
        if not predicate(path, leaf):
            return leaf
        return _pack_leaf(leaf, config.bits, _axis_for(leaf, config))

    return jax.tree_util.tree_map_with_path(one, params)


def ptq_unpack(packed: PyTree, dtype=jnp.float32) -> PyTree:
    def one(leaf):
        if isinstance(leaf, PackedTensor):
            return leaf.dequantize(dtype)
        return leaf
    return jax.tree_util.tree_map(
        one, packed, is_leaf=lambda x: isinstance(x, PackedTensor))


def tree_nbytes(params: PyTree) -> int:
    """Parameter-memory footprint (paper's 4x memory-reduction claim)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedTensor)):
        if isinstance(leaf, PackedTensor):
            total += leaf.nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total
