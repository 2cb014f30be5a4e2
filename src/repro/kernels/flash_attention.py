"""Flash attention Pallas TPU kernel (causal / sliding-window).

Knobs (Moses "attention" workload): block_q, block_kv. Grid is
(batch*heads, gq, gkv) with the kv dim innermost ("arbitrary" semantics);
running max / denominator / accumulator live in VMEM scratch across the kv
sweep — the IO-aware schedule of FlashAttention mapped onto the TPU memory
hierarchy (HBM -> VMEM tiles -> MXU).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import compiler_params

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               gkv, block_q, block_kv, causal, window, scale, seq_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    # skip fully-masked blocks (still visited; compute gated by pl.when)
    block_needed = True
    if causal:
        block_needed = (ki * block_kv) <= (qi * block_q + block_q - 1)

    @pl.when(block_needed if causal else True)
    def _compute():
        s = jnp.dot(q_ref[0], k_ref[0].T,
                    preferred_element_type=jnp.float32) * scale
        mask = k_pos < seq_len
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        # zero the masked entries with the 2-D mask: a row whose entries are
        # all masked has m_new == NEG_INF and would otherwise get exp(0) = 1.
        # (Mosaic cannot reshape a 1-D boolean to 2-D, so the row test
        # `(m_new == NEG_INF)[:, None]` does not compile for the TPU.)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == gkv - 1)
    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,   # [B, S, D]  (B folds batch*heads)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, S, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq, bkv = min(block_q, S), min(block_kv, S)
    pad_q, pad_kv = (-S) % bq, (-S) % bkv
    Sq, Skv = S + pad_q, S + pad_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0)))
    gq, gkv = Sq // bq, Skv // bkv

    out = pl.pallas_call(
        functools.partial(_fa_kernel, gkv=gkv, block_q=bq, block_kv=bkv,
                          causal=causal, window=window, scale=scale,
                          seq_len=S),
        grid=(B, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :S]
