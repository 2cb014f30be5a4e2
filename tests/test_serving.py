"""Hub serving tests: byte-offset shard indexes (sidecar persistence,
stamp/schema self-invalidation, compact-under-reader), the tuned-config LRU
and latency windows, the framed socket protocol, the hub's fine-grained
read path (a slow in-flight tune must not block hits — ISSUE 7 satellite),
and the multi-process reader/writer server end to end, including the
concurrent multi-client hammer and reader kill/respawn. Model serving: the
engine's spans under the profiler, and its weights cast to the compute
dtype once (the same logits, bitwise, for every model family).
"""
import dataclasses
import json
import multiprocessing as mp
import os
import re
import socket
import threading
import time

import pytest

from repro.autotune.registry import Registry
from repro.autotune.space import ProgramConfig, Workload, default_config
from repro.configs import ARCH_IDS
from repro.hub.serving import index as idx_mod
from repro.hub.serving import protocol
from repro.hub.serving.cache import LatencyWindow, TunedConfigCache
from repro.hub.store import RecordStore, StoreSchemaError

WL_A = Workload("matmul", (256, 256, 128), name="a")
WL_B = Workload("matmul", (512, 256, 128), name="b")
CFG_A = default_config(WL_A)
CFG_B = ProgramConfig.make(block_m=64, block_n=128, block_k=128,
                           k_inner=0, unroll=1, out_bf16=1)


def _shard_of(store, device, wl):
    return store._shard_path(device, wl.key())


class TestShardIndex:
    def test_sidecar_written_on_flush(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.put("tpu_v5e", WL_A, CFG_B, 150.0, trial=1)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        sidecar = idx_mod.index_path(shard)
        assert os.path.exists(sidecar)
        st = os.stat(shard)
        idx = idx_mod.load_index(shard, (st.st_mtime_ns, st.st_size))
        assert idx is not None
        assert idx.n_records == 2 and idx.n_good == 2
        assert idx.best(WL_A.key())["throughput_gflops"] == 150.0

    def test_rows_seek_read_exact_records(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        for t in range(5):
            store.put("tpu_v5e", WL_A, CFG_A, 100.0 + t, trial=t)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        idx = store._shard_index(shard)
        rows = idx_mod.read_rows(shard, idx, 0)
        assert [r["trial"] for r in rows] == [0, 1, 2, 3, 4]
        tail = store.tail_rows("tpu_v5e", WL_A.key(), 2)
        assert [r["trial"] for r in tail] == [3, 4]

    def test_stale_sidecar_self_invalidates(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        # a foreign process appends a better record WITHOUT updating the
        # sidecar: the stamp no longer matches, readers must re-parse
        rec = dict(json.loads(open(shard).readline()))
        rec["throughput_gflops"] = 999.0
        rec["trial"] = 7
        with open(shard, "a") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
        fresh = RecordStore(str(tmp_path / "s"))
        best = fresh.best_record("tpu_v5e", WL_A.key())
        assert best["throughput_gflops"] == 999.0
        # and the rebuilt sidecar was persisted with the new stamp
        st = os.stat(shard)
        assert idx_mod.load_index(
            shard, (st.st_mtime_ns, st.st_size)) is not None

    def test_foreign_index_version_rebuilds(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        sidecar = idx_mod.index_path(shard)
        payload = json.load(open(sidecar))
        payload["index_version"] = 999
        json.dump(payload, open(sidecar, "w"))
        st = os.stat(shard)
        assert idx_mod.load_index(
            shard, (st.st_mtime_ns, st.st_size)) is None
        fresh = RecordStore(str(tmp_path / "s"))
        assert fresh.best_record(
            "tpu_v5e", WL_A.key())["throughput_gflops"] == 100.0

    def test_corrupt_interior_line_raises(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.put("tpu_v5e", WL_A, CFG_B, 150.0)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        lines = open(shard).read().splitlines()
        lines[0] = lines[0][: len(lines[0]) // 2]
        open(shard, "w").write("\n".join(lines) + "\n")
        with pytest.raises(StoreSchemaError):
            idx_mod.build_index(shard)

    def test_torn_trailing_line_tolerated(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        with open(shard, "a") as f:
            f.write('{"schema": 1, "torn')      # writer died mid-append
        idx = idx_mod.build_index(shard)
        assert idx.n_records == 1

    def test_best_record_merges_buffered(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.flush()
        store.put("tpu_v5e", WL_A, CFG_B, 500.0, trial=1)   # unflushed
        assert store.best_record(
            "tpu_v5e", WL_A.key())["throughput_gflops"] == 500.0

    def test_count_and_task_keys_via_index(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.put("tpu_v5e", WL_B, CFG_A, 75.0)
        store.put("tpu_v5e", WL_A, CFG_B, None, error="boom")
        store.flush()
        fresh = RecordStore(str(tmp_path / "s"))
        assert fresh.count("tpu_v5e") == 2
        assert fresh.count("tpu_v5e", include_errors=True) == 3
        assert fresh.task_keys("tpu_v5e") == sorted(
            [WL_A.key(), WL_B.key()])


class TestCompactIndexInvalidation:
    def _dup_shard(self, tmp_path):
        store = RecordStore(str(tmp_path / "s"))
        store.put("tpu_v5e", WL_A, CFG_A, 100.0)
        store.put("tpu_v5e", WL_A, CFG_B, 150.0, trial=1)
        store.flush()
        shard = _shard_of(store, "tpu_v5e", WL_A)
        # simulate a second process double-appending the same rows
        body = open(shard).read()
        open(shard, "a").write(body)
        return store, shard

    def test_compact_rebuilds_sidecar_atomically(self, tmp_path):
        store, shard = self._dup_shard(tmp_path)
        assert store.compact("tpu_v5e") == 2
        st = os.stat(shard)
        idx = idx_mod.load_index(shard, (st.st_mtime_ns, st.st_size))
        assert idx is not None, "compact left a stale sidecar"
        assert idx.n_records == 2
        # shard cache + idx cache agree with disk immediately
        assert store.count("tpu_v5e") == 2
        assert store.best_record(
            "tpu_v5e", WL_A.key())["throughput_gflops"] == 150.0

    def test_compact_under_concurrent_reader(self, tmp_path):
        """Readers racing a compaction must always see a consistent
        (shard, sidecar) pair: every observed best is the true winner and
        no read ever errors on a torn index."""
        store, shard = self._dup_shard(tmp_path)
        stop = threading.Event()
        failures = []

        def _reader():
            while not stop.is_set():
                r = RecordStore(os.path.dirname(
                    os.path.dirname(os.path.dirname(shard))))
                try:
                    best = r.best_record("tpu_v5e", WL_A.key())
                    n = r.count("tpu_v5e")
                except Exception as e:  # noqa: BLE001
                    failures.append(repr(e))
                    return
                if best["throughput_gflops"] != 150.0 or n not in (2, 4):
                    failures.append(f"torn view: best={best} n={n}")
                    return

        threads = [threading.Thread(target=_reader) for _ in range(3)]
        for t in threads:
            t.start()
        for _ in range(5):      # repeated duplicate + compact cycles
            body = open(shard).read()
            open(shard, "a").write(body)
            store.compact("tpu_v5e")
        stop.set()
        for t in threads:
            t.join(10.0)
        assert not failures, failures
        assert store.count("tpu_v5e") == 2


class TestTunedConfigCache:
    def test_lru_eviction_and_counters(self):
        c = TunedConfigCache(capacity=2)
        c.put("d", "a", CFG_A, 1.0)
        c.put("d", "b", CFG_B, 2.0)
        assert c.get("d", "a") == (CFG_A, 1.0)    # refreshes 'a'
        c.put("d", "c", CFG_A, 3.0)               # evicts 'b'
        assert c.get("d", "b") is None
        assert c.get("d", "a") is not None
        k = c.counters()
        assert k["evictions"] == 1 and k["hits"] == 2 and k["misses"] == 1

    def test_invalidate_by_device(self):
        c = TunedConfigCache()
        c.put("d1", "a", CFG_A, 1.0)
        c.put("d1", "b", CFG_B, 2.0)
        c.put("d2", "a", CFG_A, 3.0)
        assert c.invalidate("d1") == 2
        assert c.get("d1", "a") is None
        assert c.get("d2", "a") is not None
        assert c.invalidate("d2", "a") == 1
        assert len(c) == 0

    def test_latency_window_percentiles(self):
        w = LatencyWindow(capacity=100)
        for ms in range(1, 101):
            w.record(ms / 1e3)
        assert w.percentile(50) == pytest.approx(0.050)
        assert w.percentile(99) == pytest.approx(0.099)
        s = w.summary()
        assert s["n"] == 100 and s["p99_ms"] == pytest.approx(99.0)


class TestProtocol:
    def test_round_trip(self):
        a, b = socket.socketpair()
        with a, b:
            protocol.send_frame(a, {"op": "ping", "x": [1, 2, 3]})
            assert protocol.recv_frame(b) == {"op": "ping", "x": [1, 2, 3]}

    def test_clean_eof_is_none_torn_is_error(self):
        a, b = socket.socketpair()
        a.close()
        with b:
            assert protocol.recv_frame(b) is None
        a, b = socket.socketpair()
        with b:
            a.sendall(b"\x00\x00")                  # half a length prefix
            a.close()
            with pytest.raises(protocol.ProtocolError):
                protocol.recv_frame(b)

    def test_oversized_frame_rejected(self):
        a, b = socket.socketpair()
        with a, b:
            a.sendall((protocol.MAX_FRAME + 1).to_bytes(4, "big"))
            with pytest.raises(protocol.ProtocolError):
                protocol.recv_frame(b)

    def test_workload_config_wire_round_trip(self):
        wl = protocol.workload_from_wire(protocol.workload_to_wire(WL_A))
        assert wl == WL_A and wl.key() == WL_A.key()
        cfg = protocol.config_from_wire(protocol.config_to_wire(CFG_B))
        assert cfg.knobs == CFG_B.knobs


class TestRegistryReload:
    def test_maybe_reload_sees_foreign_save(self, tmp_path):
        path = str(tmp_path / "reg.json")
        r1 = Registry(path=path)
        r2 = Registry(path=path)
        r1.put("d", WL_A, CFG_A, 100.0)
        r1.save()
        assert r2.lookup("d", WL_A) is None         # stale until reload
        assert r2.maybe_reload() is True
        assert r2.lookup("d", WL_A)["throughput_gflops"] == 100.0
        assert r2.maybe_reload() is False           # mtime unchanged

    def test_own_save_does_not_trigger_reload(self, tmp_path):
        r = Registry(path=str(tmp_path / "reg.json"))
        r.put("d", WL_A, CFG_A, 100.0)
        r.save()
        assert r.maybe_reload() is False


# --- hub cache wiring + fine-grained read path (ISSUE 7 satellite) --------

import types  # noqa: E402

from repro.hub.service import TuningHub  # noqa: E402

DET_CFG = ProgramConfig.make(block_m=64, block_n=64, block_k=128,
                             k_inner=1, unroll=1, out_bf16=1)


class TestHubCacheWiring:
    def _hub(self, tmp_path):
        hub = TuningHub(str(tmp_path / "hub"))
        hub.registry.put("tpu_v5e", WL_A, CFG_A, 100.0)
        return hub

    def test_cache_hit_path_zero_io(self, tmp_path):
        hub = self._hub(tmp_path)
        r1 = hub.get_config("tpu_v5e", WL_A)
        assert r1.cache_hit and r1.source == "registry"
        # after the first hit the LRU holds the winner: the repeat query
        # must touch neither the registry nor the store
        hub.registry.lookup = lambda *a: pytest.fail("registry touched")
        hub.store.best_record = lambda *a: pytest.fail("store touched")
        r2 = hub.get_config("tpu_v5e", WL_A)
        assert r2.cache_hit and r2.source == "cache"
        assert r2.config.knobs == r1.config.knobs
        assert hub.stats.hits == 2 and hub.stats.cache_hits == 1
        assert hub.hit_latency.summary()["n"] == 2

    def test_tune_landing_invalidates_cache(self, tmp_path):
        hub = self._hub(tmp_path)
        hub.get_config("tpu_v5e", WL_A)
        hub.get_config("tpu_v5e", WL_A)             # now served from cache

        def fake_tune(dev, tasks):
            for wl in tasks:
                hub.registry.put(dev, wl, DET_CFG, 500.0)
            # the job also lands a better winner for the CACHED workload
            hub.registry.put(dev, WL_A, DET_CFG, 500.0)
            return types.SimpleNamespace(total_measurements=1, tasks=[])

        hub._tune_batch = fake_tune
        r = hub.get_config("tpu_v5e", WL_B)
        assert r.source == "tuned"
        # the registry write invalidated the device's cached entries: the
        # next WL_A read must serve the NEW winner, not the stale cache
        r2 = hub.get_config("tpu_v5e", WL_A)
        assert r2.source == "registry"
        assert r2.config.knobs == DET_CFG.knobs

    def test_accepted_refresh_invalidates_cache(self, tmp_path):
        hub = self._hub(tmp_path)
        hub.get_config("tpu_v5e", WL_A)
        assert len(hub.config_cache) == 1
        hub._lifecycle = types.SimpleNamespace(
            serving_params=lambda dev: object(),
            maybe_refresh=lambda dev, current_fingerprint=None:
                types.SimpleNamespace(accepted=True))
        hub._run_refresh("tpu_v5e")
        assert hub.stats.refreshes == 1
        assert len(hub.config_cache) == 0, (
            "accepted lifecycle refresh must invalidate the device's cache")

    def test_slow_inflight_miss_does_not_block_hits(self, tmp_path):
        """Satellite regression: a tune job grinding away for a device
        must not serialize registry/cache-hit reads for that same device
        behind it — the hit path takes no hub-wide or per-device lock."""
        hub = self._hub(tmp_path)
        started, release = threading.Event(), threading.Event()

        def slow_tune(dev, tasks):
            started.set()
            assert release.wait(30), "test hung"
            for wl in tasks:
                hub.registry.put(dev, wl, DET_CFG, 500.0)
            return types.SimpleNamespace(total_measurements=1, tasks=[])

        hub._tune_batch = slow_tune
        miss = threading.Thread(
            target=lambda: hub.get_config("tpu_v5e", WL_B))
        miss.start()
        assert started.wait(10), "miss never reached the tune job"
        try:
            t0 = time.perf_counter()
            r = hub.get_config("tpu_v5e", WL_A)     # same device, hit
            dt = time.perf_counter() - t0
            assert r.cache_hit, "hit path fell through during a tune"
            assert dt < 1.0, (
                f"hit took {dt:.2f}s — serialized behind the tune lock")
        finally:
            release.set()
            miss.join(30)
        assert hub.stats.hits >= 1 and hub.stats.misses == 1


# --- the multi-process server (satellite: concurrent serving) -------------

WL_C = Workload("matmul", (128, 256, 128), name="c")    # store-only task


def _fake_tune(hub, calls):
    def fake(dev, tasks):
        calls.append(sorted(wl.key() for wl in tasks))
        time.sleep(0.2)                     # widen the client race window
        for wl in tasks:
            hub.registry.put(dev, wl, DET_CFG, 321.0)
        hub.registry.save()
        with hub._stats_lock:
            hub.stats.jobs += 1
        return types.SimpleNamespace(total_measurements=len(tasks),
                                     tasks=[])
    return fake


class TestHubServer:
    def test_end_to_end_and_concurrent_hammer(self, tmp_path):
        """One server boot, three acts: (1) serving-source semantics for a
        single client; (2) N threads racing tune-on-miss for one untuned
        workload — exactly ONE tuning job runs and every thread gets the
        deterministic winner; (3) a multi-process client hammer with zero
        torn replies."""
        from benchmarks.serve_hub_bench import _bench_client_main
        from repro.hub.serving.client import HubClient
        from repro.hub.serving.server import HubServer

        root = str(tmp_path / "hub")
        hub = TuningHub(root)
        hub.registry.put("tpu_v5e", WL_A, CFG_A, 100.0)
        hub.store.put("tpu_v5e", WL_C, CFG_B, 50.0)
        hub.store.flush()
        calls = []
        hub._tune_batch = _fake_tune(hub, calls)

        with HubServer(root, hub=hub, readers=2) as srv:
            with HubClient(root=root) as c:
                assert c.ping()
                r = c.get_config("tpu_v5e", WL_A, tune=False)
                assert r.source == "registry"
                assert r.config.knobs == CFG_A.knobs
                assert c.get_config("tpu_v5e", WL_A,
                                    tune=False).source == "cache"
                r = c.get_config("tpu_v5e", WL_C, tune=False)
                assert r.source == "store"
                assert r.config.knobs == CFG_B.knobs

            # act 2: concurrent tune-on-miss funnel, one job, one winner
            results, errs = [], []

            def _query(i):
                try:
                    with HubClient(root=root, offset=i) as cl:
                        results.append(
                            cl.get_config("tpu_v5e", WL_B, tune=True))
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

            threads = [threading.Thread(target=_query, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errs, errs
            assert len(results) == 6
            for r in results:
                assert r.config.knobs == DET_CFG.knobs, (
                    f"client saw a non-deterministic winner via {r.source}")
            assert len(calls) == 1, (
                f"in-flight dedup failed: {len(calls)} tuning jobs ran")

            # act 3: multi-process hammer over hit + store-miss paths
            ctx = mp.get_context("spawn")
            out_q = ctx.Queue()
            hit_wire = [protocol.workload_to_wire(WL_A)]
            miss_wire = [protocol.workload_to_wire(WL_C)]
            procs = [ctx.Process(target=_bench_client_main,
                                 args=(root, cid, 1.5, hit_wire, miss_wire,
                                       out_q), daemon=True)
                     for cid in range(4)]
            for p in procs:
                p.start()
            total = errors = 0
            for _ in procs:
                _cid, h, m, err = out_q.get(timeout=120)
                total += len(h) + len(m)
                errors += err
            for p in procs:
                p.join(10)
            assert errors == 0, f"{errors} torn/unexpected replies"
            assert total > 50, f"hammer barely ran: {total} requests"

            agg = srv.stats()
            assert agg["writer"]["jobs"] == 1
            assert sum(r.get("served", 0) for r in agg["readers"]) >= total

    def test_reader_kill_respawn_and_failover(self, tmp_path):
        """The farm liveness contract: a SIGKILLed reader is detected by
        the missed-heartbeat watchdog, respawned on a fresh port, and the
        endpoints file is republished so clients keep being served."""
        from repro.hub.serving.client import HubClient
        from repro.hub.serving.server import HubServer, endpoints_path

        root = str(tmp_path / "hub")
        store = RecordStore(os.path.join(root, "store"))
        reg = Registry(path=os.path.join(root, "tuned_configs.json"))
        reg.put("tpu_v5e", WL_A, CFG_A, 100.0)
        shim = types.SimpleNamespace(store=store, registry=reg)

        with HubServer(root, hub=shim, readers=2, tune_on_miss=False,
                       heartbeat_s=0.05, hb_grace_s=0.5) as srv:
            victim = srv._readers[0]
            old_port = victim.port
            victim.proc.kill()
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if srv.respawns >= 1 and srv._readers[0].port != old_port:
                    break
                time.sleep(0.1)
            assert srv.respawns >= 1, "watchdog never respawned the reader"
            eps = json.load(open(endpoints_path(root)))["readers"]
            assert all(ep["port"] != old_port for ep in eps), (
                "endpoints file still advertises the dead reader")
            # a client pointed at the STALE endpoint must fail over
            with HubClient(root=root,
                           endpoints=[{"rid": 0, "port": old_port}]) as c:
                r = c.get_config("tpu_v5e", WL_A, tune=False)
                assert r.source in ("registry", "cache")
                assert r.config.knobs == CFG_A.knobs


class TestStatsColumns:
    def test_print_stats_serving_columns(self, tmp_path, capsys):
        from repro.launch.hub import print_stats

        root = str(tmp_path / "hub")
        hub = TuningHub(root)
        hub.registry.put("tpu_v5e", WL_A, CFG_A, 100.0)
        hub.get_config("tpu_v5e", WL_A)
        hub.get_config("tpu_v5e", WL_A)
        print_stats(root, hub=hub)
        out = capsys.readouterr().out
        assert "serving cache:" in out
        assert "hit-rate=0.500" in out      # 1 LRU hit / 2 lookups
        assert "p50-ms" in out and "p99-ms" in out
        # the hit row reflects the two recorded hit latencies
        hit_row = next(ln for ln in out.splitlines()
                       if ln.strip().startswith("hit "))
        assert " 2 " in hit_row


# ---------------------------------------------------------------------------
# the model-serving engine's spans under the profiler
# ---------------------------------------------------------------------------

# (prompt length, new tokens): wave 0 is the first two, wave 1 the third
ENGINE_REQUESTS = [(5, 4), (8, 6), (3, 3)]


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """One `Engine.generate` of two waves on a tiny decoder, recorded by the
    CPU profiler: the spans, the programs that ran, the sampler's calls and
    the engine's counters."""
    import jax
    import numpy as np

    from _profiler_support import host_events, module_names, recording
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.obs import metrics as obs_metrics
    from repro.serve import Engine, Request

    cfg = get_smoke_config("h2o-danube-1.8b")
    model = build_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), mesh, max_len=32,
                 batch_slots=2)
    samples = []
    sample = eng._sample

    def counted(logits, temps):
        samples.append(logits.shape[0])
        return sample(logits, temps)

    eng._sample = counted
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(1, cfg.vocab_size, size=p
                                       ).astype(np.int32), max_new_tokens=m)
            for p, m in ENGINE_REQUESTS]
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.push_registry(reg)
    try:
        with recording(tmp_path_factory.mktemp("engine_trace")) as got:
            eng.generate(reqs)
    finally:
        obs_metrics.pop_registry(reg)
    pd = got[0]
    return types.SimpleNamespace(
        spans=host_events(pd, "serve."), modules=module_names(pd),
        samples=samples, reqs=reqs, snapshot=reg.snapshot())


def _spans(engine_trace, name):
    return [(a, b, s) for n, a, b, s in engine_trace.spans if n == name]


class TestEngineSpans:
    def test_one_step_span_per_decode_step(self, engine_trace):
        steps = _spans(engine_trace, "serve.step")
        # each wave decodes until its longest answer: 6 - 1 and 3 - 1 steps
        assert [s["step"] for _, _, s in steps] == [1, 2, 3, 4, 5, 1, 2]
        assert [s["wave"] for _, _, s in steps] == [0] * 5 + [1] * 2
        hist = engine_trace.snapshot["histograms"]
        assert hist["serve.engine.step_seconds"]["count"] == len(steps)
        tokens = engine_trace.snapshot["counters"]["serve.engine.tokens"]
        prefill_rows = sum(s["rows"] for _, _, s in
                           _spans(engine_trace, "serve.prefill"))
        assert sum(s["active_rows"] for _, _, s in steps) == \
            tokens - prefill_rows
        assert tokens == sum(m for _, m in ENGINE_REQUESTS)
        # the three phases lie inside their step, in order, and steps
        # follow each other
        for name in ("serve.step.dispatch", "serve.step.sample",
                     "serve.step.bookkeep"):
            phase = _spans(engine_trace, name)
            assert len(phase) == len(steps)
            assert all(a0 <= a <= b <= b0
                       for (a0, b0, _), (a, b, _) in zip(steps, phase))
        assert all(b <= a for (_, b, _), (a, _, _) in zip(steps, steps[1:]))

    def test_prefill_counts_match_the_prompts(self, engine_trace):
        waves = [ENGINE_REQUESTS[:2], ENGINE_REQUESTS[2:]]
        want = []
        for w, reqs in enumerate(waves):
            width = max(p for p, _ in reqs)
            real = sum(p for p, _ in reqs)
            want.append({"wave": w, "rows": len(reqs), "width": width,
                         "real_tokens": real,
                         "padded_tokens": len(reqs) * width - real,
                         "groups": 1})
        assert [s for _, _, s in _spans(engine_trace, "serve.prefill")] == \
            want
        assert [s for _, _, s in _spans(engine_trace, "serve.wave")] == [
            {k: d[k] for k in ("wave", "rows", "width")} for d in want]
        # the prefill and steps of a wave lie inside its wave span
        for a0, b0, s0 in _spans(engine_trace, "serve.wave"):
            inner = [(a, b) for name in ("serve.prefill", "serve.step")
                     for a, b, s in _spans(engine_trace, name)
                     if s["wave"] == s0["wave"]]
            assert inner and all(a0 <= a <= b <= b0 for a, b in inner)

    def test_step_programs_are_named(self, engine_trace):
        assert {"jit_serve_prefill", "jit_serve_decode"} <= \
            engine_trace.modules

    def test_sample_is_called_once_per_token_step(self, engine_trace):
        # per wave: the prefill's token, then one per decode step
        assert engine_trace.samples == [2] * 6 + [1] * 3
        assert [len(r.out_tokens) for r in engine_trace.reqs] == \
            [m for _, m in ENGINE_REQUESTS]


# ---------------------------------------------------------------------------
# serving from weights cast to the compute dtype once
# ---------------------------------------------------------------------------

# Every smoke config stores f32 and computes in bf16, so each casts; danube
# again with scanned layers (as the full-size configs serve: stacked [L, ...]
# leaves, the norm scales among them), and glm4 with its published bf16
# storage, where nothing is cast.
SERVE_CASES = [(arch, {}) for arch in ARCH_IDS] + [
    ("h2o-danube-1.8b", {"scan_layers": True}),
    ("glm4-9b", {"param_dtype": "bfloat16"})]

# leaves some step reads other than as a cast to the compute dtype: norm
# scales and biases (f32 math in apply_norm and the q/k/latent norms), the
# MoE router (f32), the sLSTM recurrences (f32), RG-LRU's lambda (softplus
# in f32) and the tanh gates of cross attention
KEPT = re.compile(
    r"(^|/)(ln\w*|final_norm|encoder_norm)/(scale|bias)$|/(router|router_bias|"
    r"r_[zifo]|lambda_raw|gate_attn|gate_mlp|q_norm|kv_norm|q_norm_scale|"
    r"k_norm_scale|ffn_norm_scale)$")

# prompt lengths of the one wave each case serves; 7 new tokens each: the
# prefill's token and 6 decode steps
SERVE_PROMPTS = [5, 9]


def _case_id(case):
    arch, over = case
    return "-".join([arch] + [f"{k}={v}" for k, v in over.items()])


def _perturbed_engine(arch, **over):
    """An engine over a smoke model whose every leaf is perturbed (no norm
    scale is 1, no gate or bias 0), with the model's frontend inputs for one
    wave of len(SERVE_PROMPTS) rows; returns (engine, raw tree)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serve import Engine

    cfg = get_smoke_config(arch).replace(**over)
    model = build_model(cfg)
    leaves, treedef = jax.tree.flatten(model.init(jax.random.PRNGKey(0)))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    raw = jax.tree.unflatten(treedef, [
        (x + 0.05 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    B, width = len(SERVE_PROMPTS), cfg.frontend_dim or cfg.d_model
    extra = {}
    if cfg.is_encoder_decoder:
        extra["encoder_embeddings"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_seq_len, width),
            jnp.bfloat16)
    elif cfg.cross_attn_every > 0:
        extra["frontend_embeddings"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.num_frontend_tokens, width),
            jnp.bfloat16)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return Engine(model, raw, mesh, max_len=32, batch_slots=B,
                  extra_batch=extra), raw


def _serve_with(eng, params):
    """One greedy wave through `eng` reading `params`: the logits of the
    prefill and each decode step, and the tokens served."""
    import numpy as np

    from repro.serve import Request

    eng.params = params
    logits_seen = []
    sample = type(eng)._sample.__get__(eng)

    def kept(logits, temps):
        logits_seen.append(np.asarray(logits))
        return sample(logits, temps)

    eng._sample = kept
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(1, eng.model.cfg.vocab_size, size=p
                                       ).astype(np.int32), max_new_tokens=7)
            for p in SERVE_PROMPTS]
    eng.generate(reqs)
    return logits_seen, [r.out_tokens for r in reqs]


def _paths(tree):
    import jax
    return ["/".join(str(k.key) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class TestServingParams:
    @pytest.mark.parametrize("case", SERVE_CASES, ids=_case_id)
    def test_served_tree_serves_the_raw_trees_logits_bitwise(self, case):
        import jax
        import numpy as np

        eng, raw = _perturbed_engine(case[0], **case[1])
        served = eng.params
        cfg = eng.model.cfg
        pairs = list(zip(_paths(raw), jax.tree.leaves(raw),
                         jax.tree.leaves(served)))
        if cfg.param_dtype == cfg.activation_dtype:
            assert all(b is a for _, a, b in pairs)
        else:
            # each leaf is cast to the compute dtype, or is the caller's own
            # array and is one that some step reads other than as a cast
            for path, a, b in pairs:
                if b.dtype == np.dtype(cfg.activation_dtype):
                    np.testing.assert_array_equal(
                        np.asarray(b), np.asarray(a.astype(b.dtype)))
                else:
                    assert b is a and KEPT.search(path), path
            assert any(b is not a for _, a, b in pairs)
        logits_s, tokens_s = _serve_with(eng, served)
        logits_r, tokens_r = _serve_with(eng, raw)
        assert tokens_s == tokens_r
        assert len(logits_s) == len(logits_r) == 7
        for a, b in zip(logits_s, logits_r):
            np.testing.assert_array_equal(a, b)

    def test_casting_every_f32_matrix_changes_the_logits(self):
        """The bitwise test catches a wrong rule: casting every f32 leaf of
        two or more dimensions also casts the stacked norm scales [L, d]."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        eng, raw = _perturbed_engine("h2o-danube-1.8b", scan_layers=True)
        naive = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.ndim >= 2 and x.dtype == jnp.float32 else x, raw)
        logits_n, _ = _serve_with(eng, naive)
        logits_r, _ = _serve_with(eng, raw)
        assert any(not np.array_equal(a, b)
                   for a, b in zip(logits_n, logits_r))

    def test_decode_program_converts_no_weight(self):
        """The decode program reads the engine's weights as they are: no
        f32->bf16 convert in it takes a cast weight's shape (a stacked
        leaf's per-layer slice inside the layer loop), while the raw f32
        tree makes one for each matrix but the embedding table, whose
        gathered rows are cast."""
        import jax
        import jax.numpy as jnp

        eng, raw = _perturbed_engine("h2o-danube-1.8b", scan_layers=True)
        B = len(SERVE_PROMPTS)
        state, _ = eng._prefill(eng.params,
                                {"tokens": jnp.ones((B, 5), jnp.int32)})
        tokens = jnp.zeros((B,), jnp.int32)
        weights, matrices = set(), set()
        for path, a, b in zip(_paths(raw), jax.tree.leaves(raw),
                              jax.tree.leaves(eng.params)):
            if a.dtype != b.dtype:
                grouped = path.startswith("stack/groups/")
                shape = tuple(a.shape[1:] if grouped else a.shape)
                weights.add(shape)
                if path != "embed":
                    matrices.add(shape)
        assert len(matrices) >= 6

        def converted(params):
            text = eng._step.lower(params, state, tokens).as_text()
            return {tuple(int(d) for d in m.split("x")) for m in re.findall(
                r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)xf32>\) -> "
                r"tensor<[0-9x]+xbf16>", text)}

        assert not converted(eng.params) & weights
        assert converted(raw) >= matrices

    def test_cast_weight_bytes_gauge(self):
        import jax

        from repro.obs import metrics as obs_metrics

        def gauge(arch, **over):
            reg = obs_metrics.MetricsRegistry()
            obs_metrics.push_registry(reg)
            try:
                eng, raw = _perturbed_engine(arch, **over)
            finally:
                obs_metrics.pop_registry(reg)
            return (reg.snapshot()["gauges"]["serve.engine.cast_weight_bytes"],
                    dict(zip(_paths(raw), jax.tree.leaves(raw))))

        got, raw = gauge("h2o-danube-1.8b", scan_layers=True)
        matrices = ["embed", "lm_head"] + [
            f"stack/groups/b0/{w}" for w in (
                "attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "mlp/wi", "mlp/wg", "mlp/wo")]
        assert got == sum(2 * raw[p].size for p in matrices)
        assert gauge("glm4-9b", param_dtype="bfloat16")[0] == 0
