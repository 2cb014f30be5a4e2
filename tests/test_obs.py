"""Unified telemetry suite (ISSUE 8): metrics registry, trace spans,
flight recorder, structured logger.

Pins the contracts the rest of the stack builds on: merge-exact
histograms (one fixed bucket grid, elementwise addition), exact
nearest-rank percentiles off the raw-sample ring, picklable snapshots,
`--stats`-vs-exposition percentile agreement (the LatencyWindow
unification), span-tree wellformedness, cross-process `remote_event`
merging, and the end-to-end `run_campaign(obs=...)` flight-recorder
artifacts with the >=95% wall-time-attribution acceptance gate.
"""
import json
import math
import os
import pickle

import pytest

from repro.obs import (FlightRecorder, LatencyWindow, MetricsRegistry,
                       Tracer, get_logger, metrics as obs_metrics,
                       remote_event, summarize_trace,
                       trace as obs_trace, validate_events)
from repro.obs.metrics import (BUCKET_BOUNDS, Histogram, delta, format_key,
                               hist_percentile, parse_key)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestInstruments:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("exec.outcomes", backend="thread", ok="true")
        c.inc()
        c.inc(2)
        assert c.value == 3
        # identical (name, labels) -> the same instrument object
        assert reg.counter("exec.outcomes", ok="true",
                           backend="thread") is c
        assert reg.counter("exec.outcomes", ok="false",
                           backend="thread") is not c
        g = reg.gauge("sched.queue_depth")
        g.set(4)
        g.add(-1)
        assert g.value == 3

    def test_histogram_exact_percentiles(self):
        h = Histogram()
        for ms in range(1, 101):
            h.observe(ms / 1e3)
        assert h.percentile(50) == pytest.approx(0.050)
        assert h.percentile(99) == pytest.approx(0.099)
        assert h.count == 100
        assert h.min == pytest.approx(0.001)
        assert h.max == pytest.approx(0.100)

    def test_histogram_merge_is_exact_bucket_addition(self):
        a, b = Histogram(), Histogram()
        for v in (1e-4, 2e-3, 5e-1):
            a.observe(v)
        for v in (3e-4, 7.0):
            b.observe(v)
        merged = Histogram()
        merged.merge_state(a.state())
        merged.merge_state(b.state())
        # order independence
        other = Histogram()
        other.merge_state(b.state())
        other.merge_state(a.state())
        assert merged.state()["counts"] == other.state()["counts"]
        assert merged.count == 5
        assert merged.total == pytest.approx(a.total + b.total)
        elementwise = [x + y for x, y in zip(a.state()["counts"],
                                             b.state()["counts"])]
        assert merged.state()["counts"] == elementwise

    def test_merged_histogram_percentile_bucket_bound(self):
        """Merging a state whose raw-sample ring was dropped in transit
        forces the bucket-resolution fallback — within one grid step above
        the exact percentile, clamped to [min, max]."""
        h = Histogram()
        for ms in range(1, 101):
            h.observe(ms / 1e3)
        st = h.state()
        st["window"] = []                # a peer that shipped buckets only
        merged = Histogram()
        merged.merge_state(st)
        p50 = merged.percentile(50)
        assert 0.001 <= p50 <= 0.100
        # one grid step of 10^(1/8): the fixed-resolution guarantee
        assert 0.050 <= p50 <= 0.050 * 10 ** (1 / 8) + 1e-9

    def test_snapshot_roundtrip_pickle_and_merge(self):
        reg = MetricsRegistry()
        reg.counter("exec.respawns", backend="process").inc(2)
        reg.histogram("exec.queue_wait_seconds",
                      backend="process").observe(0.01)
        snap = pickle.loads(pickle.dumps(reg.snapshot()))
        json.dumps(snap)                 # JSON-able by construction
        other = MetricsRegistry()
        other.merge(snap)
        assert other.counter("exec.respawns",
                             backend="process").value == 2
        h = other.histogram("exec.queue_wait_seconds", backend="process")
        assert h.count == 1 and h.percentile(50) == pytest.approx(0.01)

    def test_format_parse_key_roundtrip(self):
        key = format_key("exec.outcomes",
                         (("backend", "thread"), ("ok", "true")))
        assert key == "exec.outcomes{backend=thread,ok=true}"
        name, labels = parse_key(key)
        assert name == "exec.outcomes"
        assert dict(labels) == {"backend": "thread", "ok": "true"}
        assert parse_key("plain") == ("plain", ())

    def test_delta_between_snapshots(self):
        reg = MetricsRegistry()
        reg.counter("exec.measure_seconds_total").inc(5.0)
        before = reg.snapshot()
        reg.counter("exec.measure_seconds_total").inc(2.5)
        reg.histogram("exec.queue_wait_seconds",
                      backend="thread").observe(0.004)
        d = delta(before, reg.snapshot(), prefixes=("exec.",))
        assert d["counters"]["exec.measure_seconds_total"] == \
            pytest.approx(2.5)
        st = d["histograms"]["exec.queue_wait_seconds{backend=thread}"]
        assert st["count"] == 1
        assert hist_percentile(st, 99) == pytest.approx(0.004)

    def test_registry_stack_current(self):
        base = obs_metrics.current()
        reg = MetricsRegistry()
        obs_metrics.push_registry(reg)
        try:
            assert obs_metrics.current() is reg
        finally:
            obs_metrics.pop_registry(reg)
        assert obs_metrics.current() is base


class TestLatencyWindowUnification:
    """Satellite (b): `--stats` percentile columns and the registry
    exposition must read the SAME samples."""

    def test_stats_summary_equals_exposition(self):
        reg = MetricsRegistry()
        win = LatencyWindow(
            histogram=reg.histogram("serve.latency_seconds", path="hit"))
        for ms in (1, 2, 3, 5, 8, 13, 21, 34):
            win.record(ms / 1e3)
        s = win.summary()
        expo = reg.to_json()["histograms"][
            "serve.latency_seconds{path=hit}"]
        assert s["n"] == expo["count"] == 8
        assert s["p50_ms"] == pytest.approx(expo["p50"] * 1e3)
        assert s["p99_ms"] == pytest.approx(expo["p99"] * 1e3)

    def test_standalone_window_keeps_old_contract(self):
        win = LatencyWindow(capacity=4)
        for v in (0.4, 0.1, 0.2, 0.3):
            win.record(v)
        assert len(win) == 4 and win.count == 4
        assert win.percentile(50) == pytest.approx(0.2)
        win.record(0.5)                  # evicts 0.4
        assert len(win) == 4 and win.count == 5

    def test_text_exposition_lists_instruments(self):
        reg = MetricsRegistry()
        reg.counter("hub.hits").inc(3)
        reg.histogram("hub.latency_seconds", path="hit").observe(0.002)
        text = reg.to_text()
        assert "hub.hits 3" in text
        assert "hub.latency_seconds{path=hit}" in text


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------


class TestTracing:
    def test_noop_span_without_tracer(self):
        assert obs_trace.current_tracer() is None
        s = obs_trace.span("tune.round", device="d")
        assert s is obs_trace.NOOP_SPAN
        with s:
            assert obs_trace.current_context() is None

    def test_span_tree_and_validation(self):
        tr = Tracer()
        obs_trace.activate(tr)
        try:
            with obs_trace.span("campaign", strategy="s"):
                for i in range(2):
                    with obs_trace.span("tune.round", step=i + 1):
                        with obs_trace.span("round.measure", n=4):
                            pass
        finally:
            obs_trace.deactivate(tr)
        events = tr.events
        assert len(events) == 5
        assert validate_events(events, expect_root="campaign") == []
        rounds = [e for e in events if e["name"] == "tune.round"]
        root = next(e for e in events if e["name"] == "campaign")
        assert all(e["args"]["parent_id"] == root["args"]["span_id"]
                   for e in rounds)

    def test_exception_closes_span_with_error_status(self):
        tr = Tracer()
        obs_trace.activate(tr)
        try:
            with pytest.raises(ValueError):
                with obs_trace.span("campaign"):
                    with obs_trace.span("tune.round"):
                        raise ValueError("boom")
        finally:
            obs_trace.deactivate(tr)
        by_name = {e["name"]: e for e in tr.events}
        assert by_name["tune.round"]["args"]["status"] == "error"
        assert by_name["campaign"]["args"]["status"] == "error"
        assert validate_events(tr.events) == []

    def test_remote_event_merges_into_tree(self):
        """The farm-worker path: context by value, event dict back."""
        tr = Tracer()
        obs_trace.activate(tr)
        try:
            with obs_trace.span("campaign"):
                with obs_trace.span("round.measure"):
                    ctx = obs_trace.current_context()
                    assert ctx is not None and ctx[0] == tr.trace_id
                    ev = remote_event("exec.measure", ctx, 0.0, 0.001,
                                      status="error", worker="p1", seq=7)
                    tr.add_events([ev])
        finally:
            obs_trace.deactivate(tr)
        assert validate_events(tr.events, expect_root="campaign") == []
        meas = next(e for e in tr.events if e["name"] == "exec.measure")
        assert meas["args"]["parent_id"] == ctx[1]
        assert meas["args"]["status"] == "error"
        assert meas["args"]["span_id"].startswith("r")

    def test_validate_catches_orphans_and_double_roots(self):
        tr = Tracer()
        obs_trace.activate(tr)
        try:
            with obs_trace.span("a"):
                pass
        finally:
            obs_trace.deactivate(tr)
        events = tr.events
        orphan = remote_event("x", (tr.trace_id, "missing"), 0.0, 0.0)
        assert any("orphan" in p
                   for p in validate_events(events + [orphan]))
        second_root = remote_event("y", None, 0.0, 0.0)
        assert any("1 root" in p
                   for p in validate_events(events + [second_root]))
        assert validate_events([]) == ["no span events"]


class TestProfilerSink:
    """While a JAX profiler session records, spans are host events in its
    trace, on the device ops' clock, with their attrs as event stats."""

    @pytest.mark.parametrize("with_tracer", [False, True])
    def test_span_lands_on_the_host_plane_with_its_stats(self, tmp_path,
                                                         with_tracer):
        from _profiler_support import host_events, recording
        tr = Tracer() if with_tracer else None
        if tr is not None:
            obs_trace.activate(tr)
        try:
            with recording(tmp_path) as got:
                with obs_trace.span("serve.step", step=3, rows=16):
                    with obs_trace.span("serve.step.sample"):
                        pass
        finally:
            if tr is not None:
                obs_trace.deactivate(tr)
        evs = host_events(got[0], "serve.step")
        assert [(n, s) for n, _, _, s in evs] == [
            ("serve.step", {"step": 3, "rows": 16}),
            ("serve.step.sample", {})]
        (_, a0, a1, _), (_, b0, b1, _) = evs
        assert a0 <= b0 <= b1 <= a1
        if tr is not None:        # the Tracer keeps its own event as before
            assert [e["name"] for e in tr.events] == ["serve.step.sample",
                                                      "serve.step"]
            assert tr.events[1]["args"]["step"] == 3
            assert validate_events(tr.events, expect_root="serve.step") == []

    def test_noop_span_with_jax_imported_and_no_session(self, tmp_path):
        import jax  # noqa: F401  (the profiler hook resolves once JAX is in)
        from _profiler_support import recording
        assert obs_trace.span("serve.step", step=1) is obs_trace.NOOP_SPAN
        with recording(tmp_path):
            assert obs_trace.span("serve.step") is not obs_trace.NOOP_SPAN
        assert obs_trace.span("serve.step", step=2) is obs_trace.NOOP_SPAN

    def test_obs_imports_and_spans_without_jax(self):
        import subprocess
        import sys
        code = ("import sys; sys.modules['jax'] = None\n"
                "import repro.obs as obs\n"
                "from repro.obs import trace\n"
                "assert trace.span('serve.step', step=1) is trace.NOOP_SPAN\n"
                "tr = obs.Tracer(); trace.activate(tr)\n"
                "with trace.span('a', n=1): pass\n"
                "assert [e['name'] for e in tr.events] == ['a']\n"
                "assert sys.modules['jax'] is None\n"
                "print('ok')\n")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, PYTHONPATH=src)
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr
        assert res.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# flight recorder + the end-to-end campaign gate
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_artifacts_and_log_sink(self, tmp_path):
        root = str(tmp_path / "obs")
        with FlightRecorder(root) as rec:
            assert obs_metrics.current() is rec.registry
            with obs_trace.span("campaign"):
                obs_metrics.current().counter("sched.grants",
                                              reason="warmup").inc()
            rec.event("grant", step=1, key="d|t")
            get_logger("test-obs").warning("something odd", code=7)
        lines = [json.loads(ln) for ln in
                 open(os.path.join(root, "events.jsonl"))]
        kinds = [e["kind"] for e in lines]
        assert kinds[0] == "recorder_start" and kinds[-1] == "recorder_stop"
        assert "grant" in kinds
        log_evs = [e for e in lines if e["kind"] == "log"]
        assert any(e["msg"] == "something odd" and e["code"] == 7
                   for e in log_evs)
        snap = next(e for e in lines if e["kind"] == "metrics")["snapshot"]
        assert snap["counters"]["sched.grants{reason=warmup}"] == 1
        trace_doc = json.load(
            open(os.path.join(root, "campaign.trace.json")))
        assert validate_events(trace_doc["traceEvents"],
                               expect_root="campaign") == []
        # stop released the registry stack and the tracer
        assert obs_metrics.current() is not rec.registry
        assert obs_trace.current_tracer() is None

    def test_campaign_obs_end_to_end(self, tmp_path):
        """ISSUE 8 acceptance: run_campaign(obs=...) leaves a single-rooted
        complete trace whose summary attributes >=95% of wall time, and
        launch/obs.py --check/--summarize accept the artifacts."""
        import dataclasses

        from repro.autotune.space import Workload
        from repro.configs.moses import DEFAULT as MCFG
        from repro.launch import obs as obs_cli
        from repro.sched import run_campaign

        cfg = dataclasses.replace(MCFG, online_epochs=2,
                                  adaptation_epochs=2, population_size=32,
                                  evolution_rounds=2, top_k_measure=8)
        jobs = [("tpu_v5e", [Workload("matmul", (256, 256, 128), name="a"),
                             Workload("scan", (1024, 512), name="s")])]
        root = str(tmp_path / "obs")
        result = run_campaign(jobs, cfg, strategy="ansor-random",
                              trials_per_task=8, obs=root)
        s = result.obs_summary
        assert s is not None and s["problems"] == []
        assert s["root"] == "campaign"
        assert s["attributed_pct"] >= 95.0
        assert s["error_spans"] == 0
        assert s["by_name"]["exec.measure"]["n"] == \
            result.total_measurements
        assert s["queue_wait"]["n"] == result.total_measurements
        # summarize_trace rounds the counter to 3 decimals
        assert s["measure_seconds_simulated"] == \
            pytest.approx(result.measured_seconds, abs=5e-4)
        assert obs_cli.check(root) == 0
        assert obs_cli.print_summary(root) == 0
        # the tuning result itself is identical to an uninstrumented run
        bare = run_campaign(jobs, cfg, strategy="ansor-random",
                            trials_per_task=8)
        assert bare.curve() == result.curve()

    def test_recorder_ownership_semantics(self, tmp_path):
        """A caller-started recorder passed into run_campaign survives it
        (the caller owns stop); a path string is fully managed."""
        import dataclasses

        from repro.autotune.space import Workload
        from repro.configs.moses import DEFAULT as MCFG
        from repro.sched import run_campaign

        cfg = dataclasses.replace(MCFG, online_epochs=2,
                                  adaptation_epochs=2, population_size=32,
                                  evolution_rounds=2, top_k_measure=8)
        jobs = [("tpu_v5e",
                 [Workload("matmul", (256, 256, 128), name="a")])]
        rec = FlightRecorder(str(tmp_path / "mine")).start()
        try:
            run_campaign(jobs, cfg, strategy="ansor-random",
                         trials_per_task=8, obs=rec)
            assert not rec._stopped
            # two campaigns merge into the caller's one timeline: two
            # campaign roots, so the merged trace is deliberately NOT a
            # single tree until the caller scopes it
            run_campaign(jobs, cfg, strategy="ansor-random",
                         trials_per_task=8, obs=rec)
            roots = [e for e in rec.tracer.events
                     if e["name"] == "campaign"]
            assert len(roots) == 2
        finally:
            rec.stop()
        assert rec._stopped

    def test_summarize_trace_empty(self):
        out = summarize_trace([])
        assert out["problems"] == ["no span events"]


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------


class TestLogger:
    def test_level_control_via_env(self, monkeypatch, capsys):
        lg = get_logger("test-obs-log")
        monkeypatch.setenv("REPRO_LOG_LEVEL", "warning")
        lg.info("hidden", a=1)
        lg.warning("shown", path="/x y", n=0.5)
        err = capsys.readouterr().err
        assert "hidden" not in err
        assert "[test-obs-log] WARNING: shown" in err
        assert "path='/x y'" in err and "n=0.5" in err
        monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
        lg.debug("now visible")
        assert "now visible" in capsys.readouterr().err
        monkeypatch.setenv("REPRO_LOG_LEVEL", "off")
        lg.error("muted")
        assert capsys.readouterr().err == ""

    def test_quiet_under_pytest_by_default(self, monkeypatch, capsys):
        monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
        # PYTEST_CURRENT_TEST is set by pytest itself
        get_logger("test-obs-log").info("invisible in tests")
        assert capsys.readouterr().err == ""

    def test_get_logger_is_cached(self):
        assert get_logger("same") is get_logger("same")


# ---------------------------------------------------------------------------
# HubStats as a registry view (the hub.service rewrite)
# ---------------------------------------------------------------------------


class TestHubStatsView:
    def test_counter_backed_fields(self):
        from repro.hub.service import HubStats
        reg = MetricsRegistry()
        st = HubStats(reg)
        assert st.hits == 0
        st.inc("hits")
        st.jobs += 2                     # the += idiom tests rely on
        assert st.hits == 1 and st.jobs == 2
        assert reg.counter("hub.hits").value == 1
        assert reg.counter("hub.jobs").value == 2
        d = st.to_dict()
        assert d["hits"] == 1 and d["jobs"] == 2
        assert "hits=1" in repr(st) and "jobs=2" in repr(st)
