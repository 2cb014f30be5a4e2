"""Jit'd kernel wrappers, wired to the Moses tuning registry.

tuned_matmul / tuned_flash_attention / tuned_rg_lru look up the best config
for their workload on the target device (autotune.registry) and dispatch the
Pallas kernel with those BlockSpecs — the end of the Moses pipeline: adapted
cost model -> tuned config -> kernel launch.

Kernel time comes from a profiler trace, not from here: each kernel's
`pallas_call` carries a stable name (`matmul`, `flash_attention`,
`rg_lru`), which is the name of its op in the trace.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.autotune.registry import Registry
from repro.autotune.space import Workload
from repro.kernels import flash_attention as fa_mod
from repro.kernels import matmul as mm_mod
from repro.kernels import rg_lru as lru_mod

_registry: Optional[Registry] = None


def get_registry() -> Registry:
    global _registry
    if _registry is None:
        _registry = Registry()
    return _registry


def set_registry(r: Registry):
    global _registry
    _registry = r


def tuned_matmul(a: jax.Array, b: jax.Array, device: str = "tpu_v5e",
                 interpret: bool = False) -> jax.Array:
    M, K = a.shape
    N = b.shape[1]
    wl = Workload("matmul", (M, N, K))
    cfg = get_registry().get(device, wl).as_dict()
    return mm_mod.matmul(
        a, b,
        block_m=cfg["block_m"], block_n=cfg["block_n"], block_k=cfg["block_k"],
        k_inner=bool(cfg["k_inner"]), out_bf16=bool(cfg["out_bf16"]),
        interpret=interpret)


def tuned_flash_attention(q, k, v, causal: bool = True, window: int = 0,
                          device: str = "tpu_v5e",
                          interpret: bool = False) -> jax.Array:
    B, S, D = q.shape
    wl = Workload("attention", (S, D))
    cfg = get_registry().get(device, wl).as_dict()
    return fa_mod.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=cfg["block_q"], block_kv=cfg["block_kv"], interpret=interpret)


def tuned_rg_lru(a, x, device: str = "tpu_v5e",
                 interpret: bool = False) -> jax.Array:
    B, S, W = a.shape
    wl = Workload("scan", (S, W))
    cfg = get_registry().get(device, wl).as_dict()
    return lru_mod.rg_lru(a, x, chunk=cfg["chunk"], block_w=cfg["block_w"],
                          interpret=interpret)
