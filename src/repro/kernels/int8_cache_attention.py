"""Pallas TPU kernel: decode attention over an int8-quantized KV cache.

Beyond-paper serving hot spot (DESIGN.md §3): the paper quantizes weights for
deployment; at LLM-serving scale the KV cache dominates decode memory
traffic, so we store it as int8 codes + per-token/head scales
(models/attention.py) and fuse the dequantization into the attention kernel —
codes stream HBM->VMEM at half the bf16 bytes and are widened in-register,
never materializing an fp cache.

One (q, cache) problem per call: q (H, Dh) for a single decode position,
cache k/v (T, KV, Dh) int8 + scales (T, KV). GQA handled by the wrapper
(reshape H -> KV x G). Grid over T blocks with the online-softmax state in
VMEM scratch (same recurrence as flash_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, ks_ref, v_ref, vs_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, n_t: int, t_total: int,
            block_t: int, window: Optional[int]):
    tj = pl.program_id(0)

    @pl.when(tj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                 # (G, Dh)
    # dequantize the cache block in-register
    k = k_ref[...].astype(jnp.float32) * ks_ref[...]   # (Bt, Dh)
    v = v_ref[...].astype(jnp.float32) * vs_ref[...]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # mask: valid slots [0, pos], ring-window if any
    pos = pos_ref[0, 0]
    t_idx = tj * block_t + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_t), 1)
    valid = (t_idx <= pos) & (t_idx < t_total)
    if window is not None:
        valid &= t_idx > pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(tj == n_t - 1)
    def _done():
        lse = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[...] = (acc_ref[...] / lse).astype(o_ref.dtype)


def int8_cache_decode_attention(q: jnp.ndarray, k_codes: jnp.ndarray,
                                k_scale: jnp.ndarray, v_codes: jnp.ndarray,
                                v_scale: jnp.ndarray, pos: jnp.ndarray, *,
                                window: Optional[int] = None,
                                block_t: int = 512,
                                interpret: bool = False) -> jnp.ndarray:
    """q: (G, Dh) queries of ONE kv head group at decode position ``pos``;
    k/v codes: (T, Dh) int8 with (T, 1) scales. Returns (G, Dh)."""
    g, dh = q.shape
    t = k_codes.shape[0]
    bt = min(block_t, t)
    n_t = pl.cdiv(t, bt)
    scale = dh ** -0.5
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, n_t=n_t, t_total=t,
                          block_t=bt, window=window),
        grid=(n_t,),
        in_specs=[
            pl.BlockSpec((g, dh), lambda j: (0, 0)),
            pl.BlockSpec((bt, dh), lambda j: (j, 0)),
            pl.BlockSpec((bt, 1), lambda j: (j, 0)),
            pl.BlockSpec((bt, dh), lambda j: (j, 0)),
            pl.BlockSpec((bt, 1), lambda j: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((g, dh), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_codes, k_scale, v_codes, v_scale,
      # (1, 1), not (1,): a vmapped (ragged) pos then batches to
      # (B, 1, 1), whose block still spans the array's last two dims
      jnp.asarray(pos, jnp.int32).reshape(1, 1))
