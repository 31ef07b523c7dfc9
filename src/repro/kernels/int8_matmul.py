"""Pallas TPU kernel: W8A8 integer GEMM with int32 accumulation + dequant.

The deployment hot path of the paper's case study (Sec. 5: int8 policy
inference, 18x speedup on the RasPi) re-thought for the TPU MXU: int8 operands
feed ``lax.dot_general`` with ``preferred_element_type=int32`` (the MXU's
native 8-bit mode doubles matmul throughput on v5e), zero-point corrections
are applied with per-K-block partial sums, and the affine dequant happens once
in the epilogue — one fused kernel instead of dequantize-then-matmul.

Layout: x_q (M,K) int8 with per-tensor scale/zero; w_q (K,N) int8 with
per-output-channel (N,) scale/zero — the paper's per-tensor/per-axis split.
W4A8 weights arrive byte-packed by K halves and are unpacked in-kernel with
int32 shifts (the chip has no int8 vector shifts).

Grid is (M/bm, N/bn, K/bk) with K innermost; the int32 accumulator and the
two zero-point correction sums live in VMEM scratch across the K iterations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import affine


def _int8_matmul_kernel(*refs, n_k: int, k_total: int, k_len: int,
                        w_bits: int):
    """``refs`` = the x K-part(s), w, xs, xz, ws, wz, out, then the acc /
    sum_x / sum_w scratch.  W8A8 has one x part; W4A8 has two — the K
    halves that the low and high nibble planes of ``w`` contract with.
    ``k_len`` is the K extent of each part (its last block may overhang
    the array), ``k_total`` the true reduction length."""
    n_x = 2 if w_bits <= 4 else 1
    x_refs, w_ref = refs[:n_x], refs[n_x]
    (xs_ref, xz_ref, ws_ref, wz_ref, o_ref,
     acc_ref, sumx_ref, sumw_ref) = refs[n_x + 1:]
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sumx_ref[...] = jnp.zeros_like(sumx_ref)
        sumw_ref[...] = jnp.zeros_like(sumw_ref)

    if w_bits <= 4:
        w_parts = affine.int4_halves(w_ref[...])        # int32 nibbles
    else:
        w_parts = (w_ref[...],)
    bk = w_ref.shape[0]
    for x_ref, w in zip(x_refs, w_parts):
        x = x_ref[...]                                   # (bm, bk) int8
        if k_len % bk:
            # the last K block overhangs the array and pallas fills the
            # overhang with unspecified values: zero it (zero codes are
            # the additive identity for acc AND the zero-point sums).
            # Each operand builds its own mask from its own iota.
            k0 = k_idx * bk
            x = jnp.where(
                k0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                < k_len, x, 0)
            w = jnp.where(
                k0 + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
                < k_len, w, 0)
        # int8 codes straight into the MXU, int32 accumulation
        acc_ref[...] += jax.lax.dot_general(
            x, w.astype(jnp.int8), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        sumx_ref[...] += jnp.sum(x.astype(jnp.int32), axis=1,
                                 keepdims=True)              # (bm, 1)
        sumw_ref[...] += jnp.sum(w.astype(jnp.int32), axis=0,
                                 keepdims=True)              # (1, bn)

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        # NB: k_total is the TRUE reduction length — padded tail blocks hold
        # zero codes, which contribute nothing to acc/sums, but the
        # zero-point cross term must use the unpadded K.
        xz = xz_ref[0, 0].astype(jnp.int32)
        wz = wz_ref[0, :].astype(jnp.int32)                  # (bn,)
        corr = (acc_ref[...]
                - xz * sumw_ref[...]
                - wz[None, :] * sumx_ref[...]
                + k_total * xz * wz[None, :])
        scale = xs_ref[0, 0] * ws_ref[0, :][None, :]
        o_ref[...] = (scale * corr.astype(jnp.float32)).astype(o_ref.dtype)


def int8_matmul_pallas(x_q: jnp.ndarray, w_q: jnp.ndarray,
                       x_scale: jnp.ndarray, x_zero: jnp.ndarray,
                       w_scale: jnp.ndarray, w_zero: jnp.ndarray,
                       *, block_m: int = 256, block_n: int = 256,
                       block_k: int = 256, out_dtype=jnp.float32,
                       interpret: bool = False,
                       w_bits: int = 8) -> jnp.ndarray:
    """Dequantized (M,N) product of int8 (M,K) x (K,N).

    ``w_bits <= 4``: ``w_q`` is ``(ceil(K/2), N)`` with two int4 codes per
    byte along K (``core.affine.pack_int4``: low nibbles hold the first K
    half, high nibbles the second), unpacked in-kernel; K comes from
    ``x_q``, whose two halves enter the kernel as separate operands.

    A dimension no larger than its block is taken whole (no block is ever
    wider than the array); a longer K is tiled by ``block_k``, which must
    then be a multiple of 128 on the chip.
    """
    m, k = x_q.shape
    if w_bits <= 4:
        k_len = (k + 1) // 2
        # the second half is one column short for odd K: pad it with zero
        # codes, which meet the zero-padded high nibble of pack_int4
        x_parts = (x_q[:, :k_len],
                   jnp.pad(x_q[:, k_len:], ((0, 0), (0, 2 * k_len - k))))
    else:
        k_len = k
        x_parts = (x_q,)
    if w_q.shape[0] != k_len:
        raise ValueError(f"w_bits={w_bits} expects {k_len} weight rows for "
                         f"K={k}, got {w_q.shape}")
    n = w_q.shape[1]
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k_len)
    n_k = pl.cdiv(k_len, bk)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), n_k)

    xs = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)
    xz = jnp.asarray(x_zero, jnp.float32).reshape(1, 1)
    ws = jnp.asarray(w_scale, jnp.float32).reshape(1, n)
    wz = jnp.asarray(w_zero, jnp.float32).reshape(1, n)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel, n_k=n_k, k_total=k,
                          k_len=k_len, w_bits=w_bits),
        grid=grid,
        in_specs=[x_spec] * len(x_parts) + [
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            # int32 accumulator + zero-point correction partial sums, resident
            # in VMEM across the K reduction.
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
            pltpu.VMEM((1, bn), jnp.int32),
        ],
        interpret=interpret,
    )(*x_parts, w_q, xs, xz, ws, wz)
