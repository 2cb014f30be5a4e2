"""The benchmark's harness: everything that is not one cell's own.

`BENCHMARK.json` names each cell's configuration, traffic mix and metrics.
The harness finds each by name: `configs/<config>.json`,
`traffic/<traffic>.json`, `drivers/<driver>.py` (named by the traffic file),
`metrics/<metric>.py`, and the limits of the cell's correctness check in
`limits/<cell>.json`. Adding a configuration, a mix, a driver or a
per-layer metric is adding files and entries; no file here changes.

A driver module has one function:

    run(ctx: Context) -> Outcome

It builds the system under test from `ctx.config` and `ctx.traffic`, warms
up every shape it will use (set-up), measures for `ctx.seconds` seconds,
checks what the timed path produced against the plain reference, and
returns the end-to-end values, the counts the per-layer readers need and
the numbers compared with their limits.

A per-layer metric module has one function:

    read(ctx: Context, out: Outcome, trace: devtrace.Reduced) -> float | None

It returns None where it finds nothing to read; the harness then leaves the
metric out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """A run that cannot produce a result: it exits non-zero, prints none."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str, reported: List[str]) -> bool:
    """Whether a metric belongs to a cell: listed under `workloads`, or,
    without that key, whenever the cell reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    driver: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Any]


def resolve(cell_name: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, "bench")
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise BenchError(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    config = load_json(os.path.join(bench, "configs", w["config"] + ".json"))
    traffic = load_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    driver = load_module(os.path.join(bench, "drivers",
                                      traffic["driver"] + ".py"),
                         "bench_driver_" + traffic["driver"])
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"]
                 if applies(m, cell_name, reported)]
    readers = {m["name"]: load_module(
        os.path.join(bench, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        for m in per_layer}
    limits = load_json(os.path.join(bench, "limits", cell_name + ".json"))
    return Cell(cell_name, config, traffic, limits, int(w["chips"]), driver,
                e2e, per_layer, readers)


# ---------------------------------------------------------------- device

def load_peaks() -> dict:
    return load_json(os.path.join(BENCH, "peaks.json"))["devices"]


def check_device(chips: int) -> dict:
    """The device the run measures, with its peaks. No TPU, too few chips
    or a device kind missing from the peaks table is an error."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"the first device is {d.platform}, not a TPU")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips asked, {len(devs)} found")
    peaks = load_peaks()
    if d.device_kind not in peaks:
        raise BenchError(f"device kind {d.device_kind!r} is not in "
                         "bench/peaks.json")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peaks": peaks[d.device_kind]}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def jax_seed(seed: int):
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def fresh_dir(*parts: str) -> str:
    """An empty directory at a fixed path (a trace of an earlier run there
    is removed first)."""
    import shutil
    path = os.path.join(*parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def start_window(ctx: "Context"):
    """The window's length and, in the traced run, the profiler started on
    a fresh trace directory: (seconds, trace_dir or None). The traced
    window is the shorter `trace_seconds` of the traffic file."""
    if not ctx.trace:
        return ctx.seconds, None
    import jax
    trace_dir = fresh_dir(ctx.out_dir, "trace", ctx.cell)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # host spans only, no Python tracer
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return min(ctx.seconds, ctx.traffic["trace_seconds"]), trace_dir


def stop_window(ctx: "Context") -> None:
    if ctx.trace:
        import jax
        jax.profiler.stop_trace()


# ------------------------------------------------------------------ spans

class Spans:
    """Host spans from the benchmark's own files, around calls into the
    system. Off (no cost) in the end-to-end run; in the traced run each span
    is also a profiler annotation, on the device trace's clock, and records
    the CPU time its thread spent inside it."""

    def __init__(self, on: bool):
        self.on = on
        self.done: List[tuple] = []        # (name, thread CPU seconds)

    @contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax
        c0 = time.thread_time()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.done.append((name, time.thread_time() - c0))

    def cpu_seconds(self, prefix: str) -> List[float]:
        return [c for n, c in self.done if n.startswith(prefix)]


# ------------------------------------------------------- driver interface

@dataclasses.dataclass
class Context:
    cell: str
    config: dict
    traffic: dict
    limits: dict
    t0: float                    # perf_counter at the process's start
    seed: int
    seconds: float
    trace: bool
    device: dict
    out_dir: str                 # ignored directory for caches and traces
    spans: Spans
    interpret: bool = False      # Pallas interpret mode (CPU tests only)
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                 flush=True)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    setup_s: float
    attempted: int
    failed: int
    e2e: Dict[str, float]
    checks: List[Check]
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace_dir: Optional[str] = None
    memory_peak_bytes: Optional[int] = None
