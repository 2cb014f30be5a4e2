"""RG-LRU linear-scan Pallas TPU kernel.

Computes h_t = a_t * h_{t-1} + x_t with the width dim tiled across a parallel
grid axis and the sequence processed in chunks along an "arbitrary" grid axis;
the hidden state h is carried across chunks in VMEM scratch (no HBM round
trip — the TPU analogue of the paper's kernel-level tensor-program tuning for
recurrent workloads; knobs: chunk, block_w).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import compiler_params


def _lru_kernel(a_ref, x_ref, o_ref, h_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows and state stay 2-D (1, block_w): with a 1-D state the TPU
    # compiler aborts at block_w=128
    def body(t, h):
        a_t = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        x_t = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a_t * h + x_t
        o_ref[0, pl.ds(t, 1), :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h


def rg_lru(
    a: jax.Array,  # [B, S, W] decay factors in (0, 1]
    x: jax.Array,  # [B, S, W] gated inputs
    *,
    chunk: int = 256,
    block_w: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, S, W = a.shape
    ck, bw = min(chunk, S), min(block_w, W)
    pad_s, pad_w = (-S) % ck, (-W) % bw
    if pad_s or pad_w:
        # pad decays with 1 (carry state), inputs with 0 (no contribution)
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_w)), constant_values=1.0)
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_w)))
    Sp, Wp = S + pad_s, W + pad_w
    gc, gw = Sp // ck, Wp // bw

    out = pl.pallas_call(
        functools.partial(_lru_kernel, chunk=ck),
        grid=(B, gw, gc),
        in_specs=[
            pl.BlockSpec((1, ck, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, ck, bw), lambda b, w, c: (b, c, w)),
        ],
        out_specs=pl.BlockSpec((1, ck, bw), lambda b, w, c: (b, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Wp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="rg_lru",
    )(a, x)
    return out[:, :S, :W]
