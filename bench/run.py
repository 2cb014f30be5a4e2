#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. With `--trace 0` the
result line holds the cell's end-to-end metrics; with `--trace 1` the window
runs under the profiler and the line holds its per-layer metrics, the device
busy time and a breakdown of device time and idle gaps.

The run needs a TPU: with no TPU, too few chips, or a device kind missing
from bench/peaks.json, it exits non-zero and prints no result. Its last
lines on standard error, and the last key of the result line, give each
number compared with the plain reference beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402


def enable_compile_cache(out_dir: str) -> None:
    """JAX's persistent cache: where JAX_COMPILATION_CACHE_DIR says, else a
    fixed directory in the checkout (the cache key includes the path);
    every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(out_dir, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def result_line(cell: harness.Cell, ctx: harness.Context,
                out: harness.Outcome) -> dict:
    import devtrace
    metrics = {}
    device = {k: ctx.device[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = out.memory_peak_bytes
    line = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
            "attempted": out.attempted, "failed": out.failed}
    if not ctx.trace:
        values = dict(out.e2e, setup_s=out.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        red = devtrace.reduce_dir(out.trace_dir)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx, out, red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        line["breakdown"] = red.breakdown()
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def main(argv=None, device_check=harness.check_device, root: str = ROOT,
         interpret: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    out_dir = os.path.join(root, "bench_out")
    try:
        cell = harness.resolve(args.workload, root)
        enable_compile_cache(out_dir)
        device = device_check(cell.chips)
    except harness.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        return 2
    os.makedirs(out_dir, exist_ok=True)
    ctx = harness.Context(
        cell=cell.name, config=cell.config, traffic=cell.traffic,
        limits=cell.limits, t0=T0, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), device=device, out_dir=out_dir,
        spans=harness.Spans(bool(args.trace)), interpret=interpret)
    print(f"[bench] {cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={device['kind']} x{device['count']}",
          file=sys.stderr, flush=True)
    out = cell.driver.run(ctx)
    line = result_line(cell, ctx, out)
    print(f"[bench] total wall {time.perf_counter() - T0:.1f} s",
          file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
