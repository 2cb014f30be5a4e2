"""Driver `kset`: a model's tuned kernel set, pass after pass.

Set-up: on the first run in a checkout, a Moses campaign over the cell's
own workloads (the program calls of `launch/train.py`'s serial autotune
path, with the budget and tuning seed of the traffic file) writes its
winners to a registry under `bench_out/`; later runs reuse it. The kernels
read it through `kernels/ops.py`. Each distinct call is one jitted
`ops.tuned_*` (the eager wrappers trace and compile their Pallas kernel on
every call), named `kset_matmul` / `kset_attention` so that the trace
finds it. Operands are made from the seed in one jitted call, and one pass
warms up every shape.

Window: passes dispatched back to back (at most two in flight), for at
least `seconds`; it ends when the last pass's outputs are ready.
`kset_ms` is the window over the passes.

Check: the outputs of the last pass, every distinct call, against the plain
references in `reference.py`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time

import counts
import harness
import mixes
import reference as ref

DEVICE = "tpu_v5e"    # the tuner's name for the target chip


def registry_path(ctx: harness.Context) -> str:
    return os.path.join(ctx.out_dir, "registry", f"{ctx.cell}.json")


def campaign(ctx: harness.Context, workloads, path: str) -> None:
    """The serial autotune path of `launch.train.maybe_autotune`, over
    these workloads."""
    import jax
    from repro.autotune.dataset import generate_records, training_task_pool
    from repro.autotune.registry import Registry
    from repro.autotune.tuner import tune
    from repro.configs.moses import DEFAULT as MOSES_CFG
    from repro.core.cost_model import resolve_cost_model

    c = ctx.traffic["campaign"]
    moses_cfg = dataclasses.replace(MOSES_CFG, **c.get("moses", {}))
    pool = training_task_pool(include_archs=False)
    src = generate_records(pool, moses_cfg.source_device,
                           programs_per_task=c["source_programs_per_task"],
                           seed=0)
    model = resolve_cost_model("mlp", moses_cfg.cost_model)
    params = model.init(jax.random.PRNGKey(0))
    params, _ = model.train(params, src, epochs=c["pretrain_epochs"])
    result = tune(workloads, DEVICE, "moses", moses_cfg,
                  trials_per_task=c["trials_per_task"],
                  pretrained_params=params, source_pool=src,
                  cost_model=model, seed=c["tuning_seed"])
    reg = Registry(path)
    reg.ingest(result)
    reg.save()


def pace_by_second(ready: list) -> list:
    """The mean interval (ms) between passes whose outputs became ready in
    each whole second of the window: whether a slow window was slow
    throughout or in episodes."""
    out = []
    for sec in range(int(ready[-1]) + 1 if ready else 0):
        t = [b - a for a, b in zip(ready, ready[1:]) if sec <= b < sec + 1]
        if t:
            out.append(round(1e3 * sum(t) / len(t), 2))
    return out


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.autotune.registry import Registry
    from repro.autotune.space import Workload
    from repro.kernels import ops

    s = ctx.config["sizes"]
    calls = mixes.kset_calls(s, ctx.traffic)
    workloads = []
    for c in calls:
        if c["kind"] == "matmul":
            workloads.append(Workload("matmul", c["dims"], name=c["name"],
                                      count=c["count"]))
        else:
            B, H, G, S, D, W = c["dims"]
            workloads.append(Workload("attention", (S, D), name=c["name"],
                                      count=c["count"]))
    path = registry_path(ctx)
    if not os.path.exists(path):
        ctx.log(f"[kset] campaign over {len(workloads)} workloads")
        campaign(ctx, workloads, path)
    reg = Registry(path)
    ops.set_registry(reg)
    winners = {wl.key(): reg.get(DEVICE, wl).as_dict() for wl in workloads}
    digest = hashlib.sha256(json.dumps(winners, sort_keys=True).encode()
                            ).hexdigest()[:16]
    ctx.log(f"[kset] winners {json.dumps(winners, sort_keys=True)} "
            f"digest {digest}")

    interp = ctx.interpret

    def kset_matmul(a, b):
        return ops.tuned_matmul(a, b, device=DEVICE, interpret=interp)

    def kset_attention(q, k, v, window):
        return ops.tuned_flash_attention(q, k, v, causal=True, window=window,
                                         device=DEVICE, interpret=interp)

    matmul_fn = jax.jit(kset_matmul)
    attention_fn = jax.jit(kset_attention, static_argnums=3)

    def make_operands(key):
        ops_ = {}
        for i, c in enumerate(calls):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
            normal = lambda k, shape: jax.random.normal(  # noqa: E731
                k, shape, jnp.float32).astype(jnp.bfloat16)
            if c["kind"] == "matmul":
                M, N, K = c["dims"]
                ops_[c["name"]] = (normal(k1, (M, K)), normal(k2, (K, N)))
            else:
                B, H, G, S, D, W = c["dims"]
                # K/V at the model's KV heads, repeated to the query heads
                # (the kernel has no grouping): row b*H + h reads b*G + h//R
                kv = [jnp.repeat(normal(k, (B, G, S, D)), H // G, axis=1
                                 ).reshape(B * H, S, D) for k in (k2, k3)]
                ops_[c["name"]] = (normal(k1, (B * H, S, D)), *kv)
        return ops_

    operands = jax.jit(make_operands)(harness.jax_seed(ctx.seed))
    order = mixes.pass_order(calls)

    def call(c):
        args = operands[c["name"]]
        if c["kind"] == "matmul":
            return matmul_fn(*args)
        return attention_fn(*args, c["dims"][5])

    outs = {}

    def one_pass(spans):
        for c in order:
            with spans(f"bench.call.{c['name']}"):
                outs[c["name"]] = call(c)
        return outs[order[-1]["name"]]

    jax.block_until_ready(one_pass(harness.Spans(False)))   # compiles
    setup_s = time.perf_counter() - ctx.t0

    seconds, trace_dir = harness.start_window(ctx)
    passes, prev, ready = 0, None, []
    with ctx.spans("bench.window"):
        w0 = time.perf_counter()
        while True:
            with ctx.spans("bench.pass"):
                last = one_pass(ctx.spans)
            passes += 1
            if prev is not None:
                prev.block_until_ready()
                ready.append(time.perf_counter() - w0)
            prev = last
            if time.perf_counter() - w0 >= seconds:
                break
        jax.block_until_ready(list(outs.values()))
        window_s = time.perf_counter() - w0
    harness.stop_window(ctx)
    peak = harness.memory_peak_bytes()
    ctx.log(f"[kset] mean ms between passes ready, by second of the window: "
            f"{pace_by_second(ready)}")

    # per-call counts, with the output width each winner writes
    for c in calls:
        if c["kind"] == "matmul":
            M, N, K = c["dims"]
            out_bytes = outs[c["name"]].dtype.itemsize
            c["flops"], c["bytes"] = counts.matmul(M, N, K, out_bytes)
        else:
            B, H, G, S, D, W = c["dims"]
            c["flops"], c["bytes"] = counts.attention(B, H, G, S, D, W)
    pass_flops = sum(c["flops"] * c["count"] for c in calls)

    checks = []
    for kind in ("matmul", "attention"):
        errs = []
        for c in calls:
            if c["kind"] != kind:
                continue
            args = operands[c["name"]]
            if kind == "matmul":
                errs.append(ref.matmul_err(outs[c["name"]], *args))
            else:
                errs.append(ref.attention_err(outs[c["name"]], *args,
                                              window=c["dims"][5]))
        if errs:
            checks.append(harness.Check(f"{kind}_rel_err", max(errs),
                                        ctx.limits[f"{kind}_rel_err"]))
    return harness.Outcome(
        setup_s=setup_s, attempted=passes, failed=0,
        e2e={"kset_ms": window_s / passes * 1e3}, checks=checks,
        counts={"calls": calls, "passes": passes, "pass_flops": pass_flops},
        trace_dir=trace_dir, memory_peak_bytes=peak)
