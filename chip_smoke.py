#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: device, tuner, kernels, serve
    python chip_smoke.py --chips 4    # four chips: sharded training only

One chip, in one process (a chip belongs to one process at a time):

  device   the first JAX device must be a TPU; there is no CPU fallback.
  tuner    `launch.train.maybe_autotune` for the full h2o-danube-1.8b config
           (gradient scheduler, dry-run budget): cost-model pretraining,
           Moses adaptation and draft-then-verify scoring run on the chip.
           The measurements come from the simulator (`autotune/devices.py`).
  kernels  every `arch_tasks` workload of h2o-danube-1.8b through
           `kernels/ops.py`, compiled, with bf16 operands and the tuner's
           winners, plus both matmul schedules where the output is revisited,
           windowed and unwindowed attention at S=4096 and the RG-LRU scan at
           recurrentgemma-2b's width; each output is checked against
           `kernels/ref.py`.
  serve    the `Engine` with the full h2o-danube-1.8b config and random
           weights from --seed; the logits must be finite and prefill must
           agree with decode.

Four chips: a few AdamW steps of full-width h2o-danube-1.8b on a
`model_parallel=4` mesh, with parameters and optimizer state spread over the
four devices, and the first-step loss of a 2-layer full-width variant, which
must be the same unsharded on one device and sharded on the mesh.

Each phase prints its outcome on its own line. Any failure raises, and the
script exits non-zero. The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# the tuner's winners go here, not to the default registry at the repo root
REGISTRY = os.path.join(ROOT, "artifacts", "chip_smoke", "tuned_configs.json")
os.environ["REPRO_TUNING_REGISTRY"] = REGISTRY

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.autotune.registry import Registry  # noqa: E402
from repro.autotune.space import config_valid  # noqa: E402
from repro.autotune.tasks import arch_tasks  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import matmul as mm_mod  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import (make_optimizer, maybe_autotune,  # noqa: E402
                                perf_hints)
from repro.models import build_model  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402
from repro.serve import Engine, Request  # noqa: E402
from repro.train.data import DataConfig, data_iterator  # noqa: E402
from repro.train.train_loop import (init_train_state,  # noqa: E402
                                    make_serve_prefill, make_serve_step,
                                    make_train_step)

ARCH = "h2o-danube-1.8b"
DEVICE = "tpu_v5e"
# Kernel outputs from bf16 operands must agree with the f32 reference within
# this fraction of the reference's largest magnitude: bf16 keeps 8 mantissa
# bits, and the k-outer matmul with a bf16 output re-rounds every partial sum.
KERNEL_TOL = 2e-2
# Serving runs bf16 activations through 24 layers; the decode step and a
# prefill of one more token take different paths to the same logits.
SERVE_TOL = 5e-2
# First-step loss, unsharded vs sharded: same math, other reduction orders.
LOSS_RTOL = 1e-2


def rel_err(out, want) -> float:
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(out).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(out - want).max() / max(np.abs(want).max(), 1e-30))


def check(phase: str, what: str, err: float, tol: float) -> None:
    ok = err <= tol
    print(f"[{phase}] {'ok  ' if ok else 'FAIL'} {what}: rel err {err:.3e} "
          f"(tol {tol:g})", flush=True)
    if not ok:
        raise SystemExit(f"[{phase}] {what} disagrees with its reference")


def phase_device(chips: int, cache_dir: str):
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"[device] FAIL: the first device is {d.platform}, "
                         "not a TPU")
    if len(devs) < chips:
        raise SystemExit(f"[device] FAIL: {chips} chips asked, "
                         f"{len(devs)} found")
    return d, len(devs)


def phase_tuner(cfg):
    if os.path.exists(REGISTRY):
        os.remove(REGISTRY)
    t0 = time.perf_counter()
    maybe_autotune(DEVICE, cfg, scheduler="gradient", dry_run=True)
    reg = Registry(REGISTRY)
    keys = reg.task_keys(DEVICE)
    if not keys:
        raise SystemExit("[tuner] FAIL: the campaign stored no winner")
    by_key = {wl.key(): wl for wl in arch_tasks(cfg)}
    for key in keys:
        wl = by_key[key]
        if not config_valid(wl, reg.get(DEVICE, wl)):
            raise SystemExit(f"[tuner] FAIL: winner for {key} is outside "
                             "the knob space")
    print(f"[tuner] ok: {len(keys)} winners ({', '.join(keys)}) in "
          f"{time.perf_counter() - t0:.1f} s wall; measurements simulated "
          "by autotune/devices.py, cost model on the chip", flush=True)


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def phase_kernels(cfg, seed: int):
    key = jax.random.PRNGKey(seed)
    reg = ops.get_registry()
    reg.reload()
    for wl in arch_tasks(cfg):
        key, k1, k2, k3 = jax.random.split(key, 4)
        knobs = reg.get(DEVICE, wl).as_dict()
        if wl.kind == "matmul":
            M, N, K = wl.dims
            a, b = _normal(k1, (M, K)), _normal(k2, (K, N))
            out = ops.tuned_matmul(a, b, device=DEVICE)
            want = ref.matmul_ref(a, b)
        else:
            S, D = wl.dims
            shape = (cfg.num_heads, S, D)      # batch 1 x heads folded into B
            q, k, v = (_normal(kk, shape) for kk in (k1, k2, k3))
            out = ops.tuned_flash_attention(q, k, v, causal=True,
                                            window=cfg.sliding_window,
                                            device=DEVICE)
            want = ref.flash_attention_ref(q, k, v, causal=True,
                                           window=cfg.sliding_window)
        check("kernels", f"{wl.name} {wl.kind}{wl.dims} {knobs}",
              rel_err(out, want), KERNEL_TOL)

    # both schedules where an output block is revisited (gk > 1, gm*gn > 1),
    # and with blocks spanning dims that are not tile multiples
    for (M, N, K), blk in (((1024, 1024, 2048), 256), ((256, 128, 1024), 128),
                           ((20, 16, 6144), 128)):
        key, k1, k2 = jax.random.split(key, 3)
        a, b = _normal(k1, (M, K)), _normal(k2, (K, N))
        want = ref.matmul_ref(a, b)
        for k_inner in (0, 1):
            out = mm_mod.matmul(a, b, block_m=blk, block_n=blk, block_k=blk,
                                k_inner=bool(k_inner))
            check("kernels", f"matmul ({M},{N},{K}) block {blk} "
                  f"k_inner={k_inner}", rel_err(out, want), KERNEL_TOL)

    # causal attention at S=4096, head_dim 80, without and with a window
    B, S, D = 8, 4096, cfg.resolved_head_dim
    key, k1, k2, k3 = jax.random.split(key, 4)
    q, k, v = (_normal(kk, (B, S, D)) for kk in (k1, k2, k3))
    for window in (0, 1024):
        out = ops.tuned_flash_attention(q, k, v, causal=True, window=window,
                                        device=DEVICE)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        check("kernels", f"flash_attention ({B},{S},{D}) causal "
              f"window={window}", rel_err(out, want), KERNEL_TOL)

    # the RG-LRU scan at recurrentgemma-2b's width, f32 (the kernel reads one
    # row per step, which the compiler refuses for packed bf16)
    W = get_config("recurrentgemma-2b").lru_width
    key, k1, k2 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (2, 4096, W))) * 0.98
    x = jax.random.normal(k2, (2, 4096, W))
    check("kernels", f"rg_lru (2,4096,{W}) f32",
          rel_err(ops.tuned_rg_lru(a, x, device=DEVICE),
                  ref.rg_lru_ref(a, x)), KERNEL_TOL)


def phase_serve(cfg, seed: int, requests: int = 8, prompt_len: int = 128,
                max_new: int = 16, slots: int = 4):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    mesh = make_host_mesh()
    max_len = prompt_len + max_new + 8
    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size,
                          size=(requests, prompt_len)).astype(np.int32)

    engine = Engine(model, params, mesh, max_len=max_len, batch_slots=slots,
                    seed=seed)
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    t0 = time.perf_counter()
    engine.generate(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    if n_tok != requests * max_new or not all(
            0 <= t < cfg.vocab_size for r in reqs for t in r.out_tokens):
        raise SystemExit(f"[serve] FAIL: {n_tok} tokens for {requests} "
                         f"requests x {max_new}, or a token outside the "
                         "vocabulary")
    print(f"[serve] ok: {requests} requests, {n_tok} tokens, {dt:.1f} s wall "
          "(includes compilation; not a benchmark)", flush=True)

    # the decode step after prefill(prompt) must give the logits that
    # prefill(prompt + [t]) gives for its last position
    prefill = make_serve_prefill(model, mesh, max_len=max_len)
    step = make_serve_step(model, mesh)
    toks = jnp.asarray(prompts[:slots])
    state, logits = prefill(params, {"tokens": toks})
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _, dec = step(params, state, nxt)
    _, full = prefill(params, {"tokens": jnp.concatenate(
        [toks, nxt[:, None]], axis=1)})
    if not (np.isfinite(np.asarray(logits)).all()
            and np.isfinite(np.asarray(dec)).all()):
        raise SystemExit("[serve] FAIL: non-finite logits")
    check("serve", f"decode vs prefill logits {tuple(dec.shape)}",
          rel_err(dec, full), SERVE_TOL)


def _first_loss(cfg, mesh, batch, seed: int):
    model = build_model(cfg)
    opt = make_optimizer(cfg, 3e-3, 10)
    with perf_hints(mesh, "act"):
        state = init_train_state(model, opt, mesh, jax.random.PRNGKey(seed))
        step = make_train_step(model, opt, mesh)
        state, metrics = step(state, batch)
    return state, step, float(metrics["loss"])


def phase_train4(cfg, seed: int, steps: int = 3):
    devs = jax.devices()[:4]
    data = data_iterator(cfg, DataConfig(batch_size=8, seq_len=512,
                                         seed=seed))
    batch = jax.tree.map(jnp.asarray, next(data))

    # the comparison: a 2-layer full-width variant, one device vs the mesh
    small = cfg.replace(num_layers=2)
    one = jax.sharding.Mesh(np.array(devs[:1]).reshape(1, 1),
                            ("data", "model"))
    mesh = make_host_mesh(model_parallel=4)
    _, _, loss_one = _first_loss(small, one, batch, seed)
    _, _, loss_mesh = _first_loss(small, mesh, batch, seed)
    err = abs(loss_one - loss_mesh) / abs(loss_one)
    print(f"[train4] 2-layer first-step loss: one device {loss_one:.6f}, "
          f"mesh {loss_mesh:.6f}", flush=True)
    check("train4", "sharded vs one-device first-step loss", err, LOSS_RTOL)

    # full width on the mesh: state spread over the four devices
    state, step, loss = _first_loss(cfg, mesh, batch, seed)
    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    print("[train4] parameter + optimizer bytes per device: "
          + ", ".join(f"{b / 2**30:.2f} GiB" for b in held.values())
          + "; bytes in use per device: "
          + ", ".join(f"{(d.memory_stats() or {}).get('bytes_in_use', 0) / 2**30:.2f} GiB"
                      for d in devs), flush=True)
    if min(held.values()) < 0.5 * max(held.values()):
        raise SystemExit("[train4] FAIL: training state is not spread over "
                         "the four devices")
    losses = [loss]
    with perf_hints(mesh, "act"):
        for _ in range(steps - 1):
            state, metrics = step(state, jax.tree.map(jnp.asarray,
                                                      next(data)))
            losses.append(float(metrics["loss"]))
    if not np.isfinite(losses).all():
        raise SystemExit(f"[train4] FAIL: non-finite loss {losses}")
    print(f"[train4] ok: {steps} full-width steps on a model_parallel=4 "
          f"mesh, losses {', '.join(f'{x:.4f}' for x in losses)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    dev, count = phase_device(args.chips, cache_dir)
    cfg = get_config(ARCH)
    if args.chips == 4:
        phases = [("train4", lambda: phase_train4(cfg, args.seed))]
    else:
        phases = [("tuner", lambda: phase_tuner(cfg)),
                  ("kernels", lambda: phase_kernels(cfg, args.seed)),
                  ("serve", lambda: phase_serve(cfg, args.seed))]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"[{name}] phase done in {time.perf_counter() - t0:.1f} s "
              "wall, compilation included", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
