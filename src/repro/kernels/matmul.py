"""Tiled matmul Pallas TPU kernel — the primary auto-tuning target.

The Moses knobs map directly onto this kernel:
  block_m/n/k : BlockSpec tile sizes (VMEM working set, MXU shape)
  k_inner     : 1 -> grid (gm, gn, gk), fp32 accumulator tile in VMEM scratch,
                     single output write (the "accumulate-in-VMEM" schedule);
                0 -> grid (gk, gm, gn), k outermost, output block revisited
                     and accumulated in HBM (higher output traffic — exactly
                     the c_traffic = (2*gk-1) term the device simulator and
                     the 164-d features model)
  out_bf16    : output store dtype

Validated against ref.matmul_ref with interpret=True on CPU (tests/test_kernels.py),
compiled for a described v5e (tests/test_tpu_compile.py) and checked on the
chip by chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM a kernel may claim. The compiler's default (16 MiB on v5e)
# refuses the largest tiles `autotune.space.knob_space` admits (1024x1024x2048
# matmul tiles, 1024x1024 scan tiles); 64 MiB compiles all of them and stays
# under v5e's 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 2**20


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _matmul_kernel_kinner(a_ref, b_ref, o_ref, acc_ref, *, gk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == gk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_kernel_kouter(a_ref, b_ref, o_hbm, tile_ref, sem):
    # The pipeline never reads an output block back from HBM when the grid
    # returns to it, so the partial sum is copied in and out by hand: each
    # revisit reads the block back, and each write completes before the
    # step ends, so the next revisit sees it.
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bm, bn = tile_ref.shape
    block = o_hbm.at[pl.ds(i * bm, bm), pl.ds(j * bn, bn)]
    part = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _first():
        tile_ref[...] = part.astype(tile_ref.dtype)

    @pl.when(k > 0)
    def _revisit():
        read = pltpu.make_async_copy(block, tile_ref, sem)
        read.start()
        read.wait()
        tile_ref[...] = (tile_ref[...].astype(jnp.float32)
                         + part).astype(tile_ref.dtype)

    write = pltpu.make_async_copy(tile_ref, block, sem)
    write.start()
    write.wait()


def matmul(
    a: jax.Array,               # [M, K]
    b: jax.Array,               # [K, N]
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    k_inner: bool = True,
    out_bf16: bool = False,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    out_dtype = jnp.bfloat16 if out_bf16 else jnp.float32

    # pad to tile multiples (Pallas BlockSpecs need whole tiles)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if not k_inner:
        # the k-outer kernel copies output blocks by hand, and a copy of part
        # of an HBM array moves whole (8, 128) tiles only: a block spanning
        # an unaligned dim is rounded up, and the operands padded to it
        bm, bn = -(-bm // 8) * 8, -(-bn // 128) * 128
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    gm, gn, gk = Mp // bm, Np // bn, Kp // bk

    if k_inner:
        grid = (gm, gn, gk)
        out = pl.pallas_call(
            functools.partial(_matmul_kernel_kinner, gk=gk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=compiler_params("parallel", "parallel",
                                            "arbitrary"),
            interpret=interpret,
            name="matmul",
        )(a, b)
    else:
        grid = (gk, gm, gn)
        out = pl.pallas_call(
            _matmul_kernel_kouter,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda k, i, j: (i, k)),
                pl.BlockSpec((bk, bn), lambda k, i, j: (k, j)),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), out_dtype),
                            pltpu.SemaphoreType.DMA(())],
            compiler_params=compiler_params("arbitrary", "parallel",
                                            "parallel"),
            interpret=interpret,
            name="matmul",
        )(a, b)
    return out[:M, :N]
