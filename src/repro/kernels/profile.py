"""Per-kernel timing probes: tuned-vs-default wall time as histograms.

The ROADMAP's close-the-loop item (serving models launching the Pallas
kernels with registry-tuned BlockSpecs) needs the *observability* first:
this module runs each of the three kernels — matmul, flash attention,
rg_lru — under both the registry's tuned config and the vendor-default
config, and records the wall time per call into

    kernel.seconds{kernel=<k>,device=<dev>,config=tuned|default}

in the active metrics registry, making tuned-vs-default kernel time
visible on any scrape (`launch.obs --watch`) or flight record. The
serving `Engine(profile_kernels=True)` and the train loop
(`LoopConfig.profile_kernels`) run the probe once at startup;
`kernels/ops.py` additionally times every `tuned_*` dispatch when
`REPRO_KERNEL_PROFILE=1` (or `ops.enable_profiling()`).

Probe shapes default to small workloads; pass `workloads=` or derive them
from a model config with `model_workloads(cfg)` for representative shapes.
The kernels are compiled for the device; on the CPU, pass `interpret=True`
to run them in the Pallas interpreter.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.autotune.space import Workload, default_config
from repro.obs import metrics as obs_metrics

KERNELS: Tuple[str, ...] = ("matmul", "attention", "scan")

# workload kind per kernel name (the registry's taxonomy)
_KIND = {"matmul": "matmul", "attention": "attention", "scan": "scan"}


def default_workloads(seq: int = 64, width: int = 64,
                      head_dim: int = 32) -> Dict[str, Workload]:
    """One tiny representative workload per kernel (CI-sized)."""
    return {
        "matmul": Workload("matmul", (seq, width, width), name="probe"),
        "attention": Workload("attention", (seq, head_dim), name="probe"),
        "scan": Workload("scan", (seq, width), name="probe"),
    }


def model_workloads(model_cfg, seq: int = 64,
                    cap: int = 128) -> Dict[str, Workload]:
    """Probe workloads shaped like a model's layers, capped so the probe
    stays cheap."""
    d = min(cap, int(getattr(model_cfg, "d_model", cap)) or cap)
    heads = int(getattr(model_cfg, "num_heads", 0)) or 1
    head_dim = int(getattr(model_cfg, "head_dim", 0)) or max(1, d // heads)
    lru = int(getattr(model_cfg, "lru_width", 0)) or d
    return {
        "matmul": Workload("matmul", (seq, d, d), name="probe"),
        "attention": Workload("attention", (seq, min(cap, head_dim)),
                              name="probe"),
        "scan": Workload("scan", (seq, min(cap, lru)), name="probe"),
    }


def _probe_args(kernel: str, wl: Workload, rng: np.random.RandomState):
    import jax.numpy as jnp
    if kernel == "matmul":
        M, N, K = wl.dims
        return (jnp.asarray(rng.randn(M, K).astype(np.float32)),
                jnp.asarray(rng.randn(K, N).astype(np.float32)))
    if kernel == "attention":
        S, D = wl.dims
        return tuple(jnp.asarray(rng.randn(1, S, D).astype(np.float32))
                     for _ in range(3))
    S, W = wl.dims
    a = 1.0 / (1.0 + np.exp(-rng.randn(1, S, W))) * 0.98
    return (jnp.asarray(a.astype(np.float32)),
            jnp.asarray(rng.randn(1, S, W).astype(np.float32)))


def _run_kernel(kernel: str, args, cfg: Dict[str, int],
                interpret: bool):
    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import matmul as mm_mod
    from repro.kernels import rg_lru as lru_mod
    if kernel == "matmul":
        return mm_mod.matmul(
            args[0], args[1], block_m=cfg["block_m"],
            block_n=cfg["block_n"], block_k=cfg["block_k"],
            k_inner=bool(cfg["k_inner"]), out_bf16=bool(cfg["out_bf16"]),
            interpret=interpret)
    if kernel == "attention":
        return fa_mod.flash_attention(
            args[0], args[1], args[2], causal=True,
            block_q=cfg["block_q"], block_kv=cfg["block_kv"],
            interpret=interpret)
    return lru_mod.rg_lru(args[0], args[1], chunk=cfg["chunk"],
                          block_w=cfg["block_w"], interpret=interpret)


def profile_kernels(device: str = "tpu_v5e",
                    workloads: Optional[Dict[str, Workload]] = None,
                    registry=None,
                    metrics_registry=None,
                    interpret: bool = False,
                    repeats: int = 1,
                    seed: int = 0) -> Dict[str, Dict[str, float]]:
    """Time every kernel under its tuned AND default config; record each
    call into `kernel.seconds{kernel=,device=,config=}` histograms.

    Returns `{kernel: {"tuned": mean_s, "default": mean_s}}`. The tuned
    config comes from the kernels' dispatch registry (`kernels.ops`) —
    on a device/workload the registry has never seen, tuned == default,
    which is itself informative on a scrape (zero tuned advantage)."""
    import jax

    from repro.kernels import ops
    wls = workloads if workloads is not None else default_workloads()
    reg = registry if registry is not None else ops.get_registry()
    mreg = (metrics_registry if metrics_registry is not None
            else obs_metrics.current())
    rng = np.random.RandomState(seed)
    results: Dict[str, Dict[str, float]] = {}
    for kernel in KERNELS:
        wl = wls[kernel]
        args = _probe_args(kernel, wl, rng)
        results[kernel] = {}
        for source in ("default", "tuned"):
            cfg = (default_config(wl) if source == "default"
                   else reg.get(device, wl)).as_dict()
            hist = mreg.histogram("kernel.seconds", kernel=kernel,
                                  device=device, config=source)
            times: List[float] = []
            for _ in range(max(1, int(repeats))):
                t0 = time.perf_counter()
                out = _run_kernel(kernel, args, cfg, interpret)
                jax.block_until_ready(out)
                dt = time.perf_counter() - t0
                hist.observe(dt)
                times.append(dt)
            results[kernel][source] = sum(times) / len(times)
    return results
