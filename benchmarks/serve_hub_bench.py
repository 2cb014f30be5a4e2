"""Hub serving benchmark: indexed/cached reads vs full-shard scans + QPS.

Two acceptance claims behind `BENCH_hub.json` (ISSUE 7):

  1. READ PATH: at a 10k-record corpus, the indexed (`best_record` via the
     byte-offset sidecar) and cached (`TuningHub.get_config` LRU hit)
     lookups are >= 10x faster than the full-shard scan the seed serving
     path performed (parse every record of every shard, argmax throughput).
  2. QPS: the multi-process `HubServer` sustains the QPS floor under >= 8
     concurrent client processes with p99 latency pinned on BOTH the hit
     path (registry/cache winners) and the miss path (indexed store
     fallback, no tuning).

Gates are sized for a 1-core CI box (10+ processes time-slicing one CPU);
on real hardware the margins are far wider. `--check` exits non-zero if a
gate fails (the CI-facing mode); a standalone run also writes
`BENCH_hub.json` at the repo root.

    PYTHONPATH=src python -m benchmarks.serve_hub_bench [--records 10000]
        [--clients 8] [--readers 2] [--seconds 4] [--check]
"""
from __future__ import annotations

import argparse
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.autotune.registry import Registry
from repro.autotune.space import Workload, config_hash, random_config
from repro.hub.store import RecordStore, _load_shard_file
from repro.runtime import keep_off_accelerator

DEVICE = "tpu_v5e"

# gates (1-core CI floor; see module docstring)
SPEEDUP_FLOOR = 10.0          # indexed+cached vs full scan
QPS_FLOOR = 200.0             # aggregate across clients
HIT_P99_MS = 75.0
MISS_P99_MS = 150.0
MONITOR_OVERHEAD_PCT = 2.0    # always-on sampler+SLO duty cycle ceiling


def _tasks(n: int) -> List[Workload]:
    # strictly distinct dims => strictly distinct task keys (keys do not
    # include the name, and phase 2 needs disjoint hit/miss key sets)
    return [Workload("matmul", (128 + 64 * i, 256, 128), name=f"bench_{i}")
            for i in range(n)]


def _build_corpus(root: str, records: int, tasks: int,
                  seed: int = 0) -> Tuple[RecordStore, List[Workload]]:
    """A deterministic `records`-row corpus across `tasks` workloads:
    random configs per task, throughput a hash of (task, config) so every
    process computes identical winners."""
    store = RecordStore(os.path.join(root, "store"))
    wls = _tasks(tasks)
    rng = np.random.RandomState(seed)
    per = records // tasks
    n = 0
    for wl in wls:
        for j in range(per):
            cfg = random_config(wl, rng)
            thr = 100.0 + (config_hash(wl, cfg) % 10_000) / 10.0
            n += store.put(DEVICE, wl, cfg, thr, trial=j)
    store.flush()
    return store, wls


def _scan_best(root: str, device: str, task_key: str) -> float:
    """The seed read path this PR replaces: parse EVERY record of EVERY
    shard for the device and argmax the task's throughput. A fresh store
    per call — the old path had no cross-call cache either."""
    from repro.hub.store import workload_from_record
    store = RecordStore(os.path.join(root, "store"))
    best = -1.0
    for path in store._shard_files(device):
        for rec in _load_shard_file(path):
            if rec.get("error") or rec.get("throughput_gflops") is None:
                continue
            if workload_from_record(rec).key() == task_key:
                best = max(best, float(rec["throughput_gflops"]))
    return best


def bench_read_path(root: str, store: RecordStore, wls: List[Workload],
                    lookups: int = 30) -> Dict[str, float]:
    """Phase 1: scan vs indexed vs cached lookup latency at the corpus."""
    keys = [wl.key() for wl in wls]

    t0 = time.perf_counter()
    scan_n = max(3, lookups // 10)          # the scan is the slow one
    for i in range(scan_n):
        _scan_best(root, DEVICE, keys[i % len(keys)])
    scan_us = (time.perf_counter() - t0) / scan_n * 1e6

    # indexed: fresh store per call -> sidecar load + seek, no full parse
    t0 = time.perf_counter()
    for i in range(lookups):
        s = RecordStore(os.path.join(root, "store"))
        s.best_record(DEVICE, keys[i % len(keys)])
    indexed_us = (time.perf_counter() - t0) / lookups * 1e6

    # cached: the hub's LRU hit path (registry pre-warmed with winners)
    from repro.hub.service import TuningHub
    reg = Registry(path=os.path.join(root, "tuned_configs.json"))
    for wl in wls:
        best = store.best_record(DEVICE, wl.key())
        from repro.hub.serving import protocol
        reg.put(DEVICE, wl, protocol.config_from_wire(best["knobs"]),
                float(best["throughput_gflops"]))
    reg.save()
    hub = TuningHub(root, registry=reg, store=store)
    for wl in wls:                          # populate the LRU
        hub.get_config(DEVICE, wl, flush=False)
    t0 = time.perf_counter()
    for i in range(lookups * 10):
        hub.get_config(DEVICE, wls[i % len(wls)], flush=False)
    cached_us = (time.perf_counter() - t0) / (lookups * 10) * 1e6
    assert hub.stats.cache_hits >= lookups * 10, "cache hit path not taken"

    return {"scan_us": scan_us, "indexed_us": indexed_us,
            "cached_us": cached_us,
            "indexed_speedup": scan_us / max(indexed_us, 1e-9),
            "cached_speedup": scan_us / max(cached_us, 1e-9)}


def _bench_client_main(root: str, cid: int, seconds: float,
                       hit_keys: List[Dict], miss_keys: List[Dict],
                       out_q) -> None:
    """Load-generator process (spawn target): alternate hit-path and
    miss-path requests against the serving farm, reporting per-path
    latencies."""
    keep_off_accelerator()
    from repro.hub.serving import protocol
    from repro.hub.serving.client import HubClient
    hits = [protocol.workload_from_wire(w) for w in hit_keys]
    misses = [protocol.workload_from_wire(w) for w in miss_keys]
    lat: Dict[str, List[float]] = {"hit": [], "miss": []}
    errors = 0
    deadline = time.perf_counter() + seconds
    with HubClient(root=root, offset=cid) as c:
        i = 0
        while time.perf_counter() < deadline:
            wl = hits[i % len(hits)] if i % 2 == 0 else \
                misses[i % len(misses)]
            path = "hit" if i % 2 == 0 else "miss"
            try:
                r = c.get_config(DEVICE, wl, tune=False)
                lat[path].append(r.latency_s)
                if path == "hit":
                    assert r.source in ("cache", "registry"), r.source
                else:
                    assert r.source == "store", r.source
            except (ConnectionError, RuntimeError, AssertionError):
                errors += 1
            i += 1
    out_q.put((cid, lat["hit"], lat["miss"], errors))


def _pctl(xs: List[float], p: float) -> float:
    if not xs:
        return float("nan")
    xs = sorted(xs)
    return xs[max(0, min(len(xs) - 1, math.ceil(p / 100 * len(xs)) - 1))]


def bench_qps(root: str, store: RecordStore, wls: List[Workload],
              clients: int, readers: int,
              seconds: float) -> Dict[str, float]:
    """Phase 2: the multi-process farm under concurrent client load. Half
    the tasks are registry winners (hit path), half only have store
    records (miss path, no tuning)."""
    import multiprocessing as mp

    from repro.hub.serving import protocol
    from repro.hub.serving.server import HubServer

    half = len(wls) // 2
    hit_wls, miss_wls = wls[:half], wls[half:]
    reg = Registry(path=os.path.join(root, "tuned_configs.json"))
    reg._data = {}                          # only the hit half is tuned
    for wl in hit_wls:
        best = store.best_record(DEVICE, wl.key())
        reg.put(DEVICE, wl, protocol.config_from_wire(best["knobs"]),
                float(best["throughput_gflops"]))
    reg.save()

    class _ServeOnly:                       # no writer hub: reads only
        pass
    shim = _ServeOnly()
    shim.store = store
    shim.registry = reg

    hit_wire = [protocol.workload_to_wire(w) for w in hit_wls]
    miss_wire = [protocol.workload_to_wire(w) for w in miss_wls]
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    with HubServer(root, hub=shim, readers=readers, tune_on_miss=False,
                   monitor_interval_s=0.5) as srv:
        procs = [ctx.Process(target=_bench_client_main,
                             args=(root, cid, seconds, hit_wire, miss_wire,
                                   out_q), daemon=True)
                 for cid in range(clients)]
        t0 = time.perf_counter()
        for p in procs:
            p.start()
        hit_lat: List[float] = []
        miss_lat: List[float] = []
        errors = 0
        for _ in procs:
            _cid, h, m, err = out_q.get(timeout=seconds + 300)
            hit_lat.extend(h)
            miss_lat.extend(m)
            errors += err
        elapsed = time.perf_counter() - t0
        for p in procs:
            p.join(10.0)
        # monitoring overhead: CPU seconds the farm spent scraping over
        # the load window — parent merge cost (side=parent) plus every
        # reader's snapshot-handling cost (side=reader, shipped back in
        # the merged scrape). Deterministic, unlike a noisy QPS A/B, and
        # unlike wall time it doesn't count the scrape RPC *queueing*
        # behind client traffic (that is serving time, not monitoring).
        from repro.obs.timeseries import _key_matches
        snap = srv._scrape_snapshot()
        scrape_s = sum(float(st.get("total", 0.0))
                       for key, st in snap.get("histograms", {}).items()
                       if _key_matches(key, "serve.scrape_seconds"))
    overhead_pct = 100.0 * scrape_s / max(elapsed, 1e-9)
    total = len(hit_lat) + len(miss_lat)
    return {"clients": float(clients), "readers": float(readers),
            "requests": float(total), "errors": float(errors),
            "qps": total / max(elapsed, 1e-9),
            "monitor_overhead_pct": overhead_pct,
            "hit_p50_ms": _pctl(hit_lat, 50) * 1e3,
            "hit_p99_ms": _pctl(hit_lat, 99) * 1e3,
            "miss_p50_ms": _pctl(miss_lat, 50) * 1e3,
            "miss_p99_ms": _pctl(miss_lat, 99) * 1e3}


def run(records: int = 10000, tasks: int = 20, clients: int = 8,
        readers: int = 2, seconds: float = 4.0,
        seed: int = 0) -> Dict[str, float]:
    root = tempfile.mkdtemp(prefix="serve_hub_bench_")
    try:
        store, wls = _build_corpus(root, records, tasks, seed=seed)
        n = store.count(DEVICE)
        print(f"# corpus: {n} records across {tasks} tasks")

        read = bench_read_path(root, store, wls)
        print(f"# scan {read['scan_us']:.0f}us  indexed "
              f"{read['indexed_us']:.0f}us ({read['indexed_speedup']:.1f}x)"
              f"  cached {read['cached_us']:.1f}us "
              f"({read['cached_speedup']:.1f}x)")

        qps = bench_qps(root, store, wls, clients, readers, seconds)
        print(f"# {clients} clients x {seconds:.0f}s: "
              f"{qps['requests']:.0f} reqs, {qps['qps']:.0f} QPS, "
              f"hit p50/p99 {qps['hit_p50_ms']:.2f}/"
              f"{qps['hit_p99_ms']:.2f}ms, miss p50/p99 "
              f"{qps['miss_p50_ms']:.2f}/{qps['miss_p99_ms']:.2f}ms, "
              f"{qps['errors']:.0f} errors, monitor overhead "
              f"{qps['monitor_overhead_pct']:.2f}%")

        read_ok = (read["indexed_speedup"] >= SPEEDUP_FLOOR
                   and read["cached_speedup"] >= SPEEDUP_FLOOR)
        qps_ok = (qps["qps"] >= QPS_FLOOR and qps["errors"] == 0
                  and qps["hit_p99_ms"] <= HIT_P99_MS
                  and qps["miss_p99_ms"] <= MISS_P99_MS
                  and qps["monitor_overhead_pct"] <= MONITOR_OVERHEAD_PCT)
        metrics = {
            "records": float(n),
            "scan_us_per_lookup": round(read["scan_us"], 1),
            "indexed_us_per_lookup": round(read["indexed_us"], 1),
            "cached_us_per_lookup": round(read["cached_us"], 2),
            "indexed_speedup": round(read["indexed_speedup"], 1),
            "cached_speedup": round(read["cached_speedup"], 1),
            "qps": round(qps["qps"], 1),
            "qps_floor": QPS_FLOOR,
            "requests": qps["requests"],
            "errors": qps["errors"],
            "clients": qps["clients"],
            "readers": qps["readers"],
            "hit_p50_ms": round(qps["hit_p50_ms"], 3),
            "hit_p99_ms": round(qps["hit_p99_ms"], 3),
            "miss_p50_ms": round(qps["miss_p50_ms"], 3),
            "miss_p99_ms": round(qps["miss_p99_ms"], 3),
            "monitor_overhead_pct": round(qps["monitor_overhead_pct"], 3),
            "read_ok": float(read_ok),
            "qps_ok": float(qps_ok),
            "ok": float(read_ok and qps_ok),
        }
        if not read_ok:
            print(f"# READ GATE FAILED: indexed "
                  f"{read['indexed_speedup']:.1f}x / cached "
                  f"{read['cached_speedup']:.1f}x < {SPEEDUP_FLOOR}x")
        if not qps_ok:
            print(f"# QPS GATE FAILED: {qps['qps']:.0f} QPS "
                  f"(floor {QPS_FLOOR}), hit p99 {qps['hit_p99_ms']:.1f}ms "
                  f"(<= {HIT_P99_MS}), miss p99 {qps['miss_p99_ms']:.1f}ms "
                  f"(<= {MISS_P99_MS}), errors {qps['errors']:.0f}, "
                  f"monitor overhead {qps['monitor_overhead_pct']:.2f}% "
                  f"(<= {MONITOR_OVERHEAD_PCT}%)")
        return metrics
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(records: int = 10000, tasks: int = 20, clients: int = 8,
         readers: int = 2, seconds: float = 4.0, check: bool = False,
         seed: int = 0) -> int:
    metrics = run(records=records, tasks=tasks, clients=clients,
                  readers=readers, seconds=seconds, seed=seed)
    from benchmarks.run import write_bench_json
    write_bench_json("hub", metrics)
    if check and not metrics["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--records", type=int, default=10000)
    ap.add_argument("--tasks", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--readers", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero if an acceptance gate fails")
    args = ap.parse_args()
    sys.exit(main(records=args.records, tasks=args.tasks,
                  clients=args.clients, readers=args.readers,
                  seconds=args.seconds, check=args.check, seed=args.seed))
