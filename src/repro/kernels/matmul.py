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

Blocks need not divide the shape, and the operands are never padded or
copied: the grid is cdiv(M, bm) x cdiv(N, bn) x cdiv(K, bk), and the edge
blocks get partial windows. Reads past an array's end are unspecified and
writes past it are dropped, so a ragged M or N edge only computes rows and
columns that nobody keeps. A ragged K edge would sum the unspecified values
into real outputs, so on the last k step alone the kernel zeroes A's columns
and B's rows past K (`_block_dot`); where bk divides K that code is not
emitted. The k-outer kernel still writes a padded output (its hand copies
move whole (8, 128) tiles) and slices it back to (M, N).

Validated against ref.matmul_ref with interpret=True on CPU (tests/test_kernels.py),
compiled for a described v5e (tests/test_tpu_compile.py) and checked on the
chip by chip_smoke.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
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


def _block_dot(a_ref, b_ref, k_valid=0):
    """The f32 product of the A and B blocks. Where k_valid is given, only
    the first k_valid columns of A and rows of B are real (the last block of
    a ragged K edge): the rest hold whatever lies past the arrays, maybe
    NaN, so they are selected away, not multiplied by zero. A is selected in
    f32 (exactly, both ways): Mosaic cannot select in a bf16 block of fewer
    than 16 rows, such as a 4-row lm_head call's."""
    a, b = a_ref[...], b_ref[...]
    if k_valid:
        a = jnp.where(lax.broadcasted_iota(jnp.int32, a.shape, 1) < k_valid,
                      a.astype(jnp.float32), 0).astype(a.dtype)
        b = jnp.where(lax.broadcasted_iota(jnp.int32, b.shape, 0) < k_valid,
                      b, 0)
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _matmul_kernel_kinner(a_ref, b_ref, o_ref, acc_ref, *, gk, k_rem):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(k_valid=0):
        acc_ref[...] += _block_dot(a_ref, b_ref, k_valid)

    if k_rem:
        # each branch adds its own product: a `lax.cond` that returned the
        # product made danube's ffn_out kernel 36% slower on a v5e
        pl.when(k < gk - 1)(accumulate)
        pl.when(k == gk - 1)(functools.partial(accumulate, k_rem))
    else:
        accumulate()

    @pl.when(k == gk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _matmul_kernel_kouter(a_ref, b_ref, o_hbm, tile_ref, sem, *, gk, k_rem):
    # The pipeline never reads an output block back from HBM when the grid
    # returns to it, so the partial sum is copied in and out by hand: each
    # revisit reads the block back, and each write completes before the
    # step ends, so the next revisit sees it.
    k, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bm, bn = tile_ref.shape
    block = o_hbm.at[pl.ds(i * bm, bm), pl.ds(j * bn, bn)]
    if k_rem:
        part = lax.cond(k == gk - 1, lambda: _block_dot(a_ref, b_ref, k_rem),
                        lambda: _block_dot(a_ref, b_ref))
    else:
        part = _block_dot(a_ref, b_ref)

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

    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if not k_inner:
        # the k-outer kernel copies output blocks by hand, and a copy of part
        # of an HBM array moves whole (8, 128) tiles only: a block spanning
        # an unaligned dim is rounded up, and the output padded to it
        bm, bn = -(-bm // 8) * 8, -(-bn // 128) * 128
    gm, gn, gk = pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk)
    k_rem = K % bk

    if k_inner:
        return pl.pallas_call(
            functools.partial(_matmul_kernel_kinner, gk=gk, k_rem=k_rem),
            grid=(gm, gn, gk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=compiler_params("parallel", "parallel",
                                            "arbitrary"),
            interpret=interpret,
            name="matmul",
        )(a, b)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel_kouter, gk=gk, k_rem=k_rem),
        grid=(gk, gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda k, i, j: (i, k)),
            pl.BlockSpec((bk, bn), lambda k, i, j: (k, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), out_dtype),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=compiler_params("arbitrary", "parallel",
                                        "parallel"),
        interpret=interpret,
        name="matmul",
    )(a, b)
    return out[:M, :N]
