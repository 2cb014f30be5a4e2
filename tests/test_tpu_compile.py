"""Compile-only guards: the main-path Pallas kernels at h2o-danube-1.8b
widths, compiled for a described TPU v5e. Nothing runs; the TPU compiler
refuses what interpret mode accepts (tiles the lowering cannot lay out,
VMEM overflow, shape casts Mosaic lacks). Each kernel's custom call
carries its `pallas_call` name, which is the op's name in a device trace.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.rg_lru import rg_lru

D_MODEL, D_FF, HEAD_DIM, LRU_WIDTH = 2560, 6912, 80, 2560


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs
        try:                                     # under the temp directory
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, kernel, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls
    named = re.compile(rf"^\s*(ROOT )?%{kernel}(\.\d+)? = .*custom-call\(")
    assert all(named.match(ln) for ln in calls), calls
    return hlo


@pytest.mark.parametrize("k_inner", [False, True])
@pytest.mark.parametrize("mnk,blocks", [
    ((2048, D_FF, D_MODEL), (128, 128, 128)),
    ((2048, D_FF, D_MODEL), (1024, 1024, 2048)),
    # blocks spanning dims that are not tile multiples: 20 rows (an expert's
    # share of tokens) by 16 columns (a router over 16 experts)
    ((20, 16, 6144), (32, 128, 256)),
    # danube's lm_head: 4 rows, and 2560 in blocks of 1024 leave a ragged K
    # edge, masked in a bf16 A block of fewer than 16 rows
    ((4, 32000, 2560), (8, 1024, 1024)),
], ids=["default", "largest", "whole-dim", "ragged-k-4-rows"])
def test_matmul_compiles(one_chip, k_inner, mnk, blocks):
    (M, N, K), (bm, bn, bk) = mnk, blocks
    fn = functools.partial(matmul, block_m=bm, block_n=bn, block_k=bk,
                           k_inner=k_inner)
    _compile(fn, "matmul", one_chip, ((M, K), jnp.bfloat16),
             ((K, N), jnp.bfloat16))


@pytest.mark.parametrize("k_inner", [False, True])
def test_matmul_ragged_edge_copies_no_weight(one_chip, k_inner):
    """GLM-4-9B's ffn_in at 64 decode rows: 27392 columns in blocks of 1024
    leave a ragged edge, which the kernel takes as a partial block. The
    weight goes to the kernel where it lies: nothing pads either operand,
    and nothing slices the weight."""
    M, N, K = 64, 27392, 4096
    fn = functools.partial(matmul, block_m=64, block_n=1024, block_k=2048,
                           k_inner=k_inner)
    hlo = _compile(fn, "matmul", one_chip, ((M, K), jnp.bfloat16),
                   ((K, N), jnp.bfloat16))
    weight = re.search(rf"(%\S+) = bf16\[{K},{N}\]\S* parameter\(1\)",
                       hlo).group(1)
    use = re.compile(rf"{re.escape(weight)}[,)]")
    assert not re.search(r"\bpad\(", hlo)
    assert not [ln for ln in hlo.splitlines()
                if re.search(r"\bslice\(", ln) and use.search(ln)]
    assert [ln for ln in hlo.splitlines()
            if "custom-call(" in ln and use.search(ln)]


@pytest.mark.parametrize("head_dim", [HEAD_DIM, 128])
@pytest.mark.parametrize("window", [0, 1024])
def test_flash_attention_compiles(one_chip, head_dim, window):
    shape = ((8, 4096, head_dim), jnp.bfloat16)
    fn = functools.partial(flash_attention, causal=True, window=window)
    _compile(fn, "flash_attention", one_chip, shape, shape, shape)


@pytest.mark.parametrize("chunk,block_w", [(256, 128), (1024, 1024)])
def test_rg_lru_compiles(one_chip, chunk, block_w):
    shape = ((2, 4096, LRU_WIDTH), jnp.float32)
    fn = functools.partial(rg_lru, chunk=chunk, block_w=block_w)
    _compile(fn, "rg_lru", one_chip, shape, shape)
