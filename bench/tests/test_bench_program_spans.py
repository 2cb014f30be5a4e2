"""The readers of the program's own spans and names in a profiler trace
(`progtrace.py`): a decode step's device time, the device's idle time per
decode step, the prefill's padding share and the kernel wrappers' share,
on hand-made traces, a hand-written XSpace, the spans a tiny engine writes
under the CPU profiler, and a few decode steps recorded on a TPU v5e."""
import json
import os

import pytest

import tiny
import harness
import mixes
import progtrace

METRICS = ("serve.decode.step_device_ms", "serve.decode.host_gap_ms",
           "serve.prefill.pad_share", "kset.wrapper_share")


def reader(metric):
    return harness.load_module(
        os.path.join(tiny.BENCH, "metrics", metric + ".py"),
        "m_" + metric.replace(".", "_"))


def read(monkeypatch, metric, trace):
    """The metric read from a Trace as if it were the run's."""
    red = progtrace.Reduced(trace)
    monkeypatch.setattr(progtrace, "for_outcome", lambda out: red)
    out = harness.Outcome(setup_s=0, attempted=1, failed=0, e2e={},
                          checks=[], trace_dir="unused")
    return reader(metric).read(None, out, None)


def trace(spans, ops=(), modules=()):
    return progtrace.Trace.from_json({"spans": spans, "ops": list(ops),
                                      "modules": list(modules)})


def decode_trace():
    """Two decode steps in a window of 0..100 ns: each runs the decode
    program (with two overlapping ops) and then a sampler program; the host
    leaves the device idle between them."""
    spans = [["bench.window", 0, 100, {}],
             ["serve.step", 10, 50, {"step": 1, "active_rows": 2}],
             ["serve.step", 50, 95, {"step": 2, "active_rows": 1}],
             ["serve.step.sample", 30, 48, {}]]
    ops = [["%fusion.1 = f32[8]{0} fusion(a)", 12, 22],
           ["%copy.1 = f32[8]{0} copy(b)", 18, 26],          # overlaps
           ["%argmax.1 = s32[2]{0} reduce(c)", 30, 32],      # sampler
           ["%fusion.1 = f32[8]{0} fusion(a)", 55, 65],
           ["%argmax.1 = s32[2]{0} reduce(c)", 70, 72]]
    modules = [["jit_serve_decode(1)", 12, 26],
               ["jit_argmax(2)", 30, 32],
               ["jit_serve_decode(1)", 55, 65],
               ["jit_argmax(2)", 70, 72]]
    return trace(spans, ops, modules)


def test_decode_step_device_time_is_the_busy_union_per_run(monkeypatch):
    got = read(monkeypatch, "serve.decode.step_device_ms", decode_trace())
    # (26 - 12) + (65 - 55) ns over 2 runs; the sampler is not the step
    assert got == pytest.approx(12e-6)


def test_idle_is_split_across_step_spans(monkeypatch):
    got = read(monkeypatch, "serve.decode.host_gap_ms", decode_trace())
    # step 1: 40 ns with 14 + 2 busy; step 2: 45 ns with 10 + 2 busy
    assert got == pytest.approx(((40 - 16) + (45 - 12)) / 2 * 1e-6)


def test_device_time_and_idle_sum_to_the_step_time(monkeypatch):
    """With no sampler program, a step's time is its device time plus the
    device's idle time inside it."""
    spans = [["bench.window", 0, 100, {}],
             ["serve.step", 0, 40, {}], ["serve.step", 40, 80, {}]]
    ops = [["%fusion.1 = f32[8]{0} fusion(a)", 5, 30],
           ["%fusion.1 = f32[8]{0} fusion(a)", 45, 70]]
    mods = [["jit_serve_decode(1)", 5, 30], ["jit_serve_decode(1)", 45, 70]]
    t = trace(spans, ops, mods)
    device = read(monkeypatch, "serve.decode.step_device_ms", t)
    gap = read(monkeypatch, "serve.decode.host_gap_ms", t)
    assert device + gap == pytest.approx(40e-6)


def test_step_span_straddling_the_window_edge_is_clipped(monkeypatch):
    spans = [["bench.window", 20, 100, {}],
             ["serve.step", 0, 40, {"step": 1}],     # starts before it
             ["serve.step", 40, 80, {"step": 2}],
             ["serve.step", 80, 130, {"step": 3}],   # ends after it
             ["serve.step", 130, 170, {"step": 4}]]  # outside
    ops = [["%fusion.1 = f32[8]{0} fusion(a)", 10, 30],
           ["%fusion.1 = f32[8]{0} fusion(a)", 50, 70],
           ["%fusion.1 = f32[8]{0} fusion(a)", 90, 120]]
    red = progtrace.Reduced(trace(spans, ops))
    assert [(s.start, s.end) for s in red.named("serve.step")] == [
        (20, 40), (40, 80), (80, 100)]
    got = read(monkeypatch, "serve.decode.host_gap_ms", trace(spans, ops))
    # idle inside the window: 30..50, 70..90 = 40 ns over 3 steps
    assert got == pytest.approx(40 / 3 * 1e-6)


def test_wrapper_share_counts_the_ops_around_named_kernels(monkeypatch):
    """Kernel and other ops in one `jit_kset_matmul` run; a kernel op
    without a kernel's name (an older checkout's) is not a kernel."""
    spans = [["bench.window", 0, 200, {}]]
    ops = [["%pad.0 = bf16[64,256]{1,0} pad(b, c)", 10, 40],
           ["%matmul.1 = f32[64,256]{1,0} custom-call(a, pad.0), "
            "custom_call_target=\"tpu_custom_call\"", 40, 70],
           ["%slice.0 = f32[64,200]{1,0} slice(matmul.1)", 70, 80],
           ["%flash_attention.1 = f32[8,128]{1,0} custom-call(q, k, v)",
            100, 130],
           ["%fusion.9 = f32[8]{0} fusion(x)", 150, 160]]   # other program
    mods = [["jit_kset_matmul(1)", 10, 80],
            ["jit_kset_attention(2)", 100, 130],
            ["jit_other(3)", 150, 160]]
    got = read(monkeypatch, "kset.wrapper_share", trace(spans, ops, mods))
    assert got == pytest.approx(100 * (30 + 10) / (70 + 30))


def test_pad_share_sums_the_prefill_spans(monkeypatch):
    spans = [["bench.window", 0, 100, {}],
             ["serve.prefill", 10, 20, {"rows": 4, "width": 4096,
                                        "real_tokens": 8192,
                                        "padded_tokens": 8192}],
             ["serve.prefill", 40, 50, {"rows": 4, "width": 2048,
                                        "real_tokens": 5120,
                                        "padded_tokens": 3072}]]
    got = read(monkeypatch, "serve.prefill.pad_share", trace(spans))
    assert got == pytest.approx(100 * (8192 + 3072) / (4 * 4096 + 4 * 2048))


@pytest.mark.parametrize("metric", METRICS)
def test_readers_find_nothing_in_an_older_programs_trace(monkeypatch,
                                                         metric):
    """The parent's trace: bench spans only, step programs named `jit_fn`,
    unnamed kernels. Each reader returns None rather than a number."""
    spans = [["bench.window", 0, 100, {}], ["bench.wave", 5, 95, {}]]
    ops = [["%fusion.1 = f32[8]{0} fusion(a)", 10, 30],
           ["%kset_matmul.1 = f32[8,128]{1,0} custom-call(a, b)", 40, 60]]
    mods = [["jit_fn(1)", 10, 30], ["jit_kset_matmul(2)", 40, 60]]
    assert read(monkeypatch, metric, trace(spans, ops, mods)) is None
    monkeypatch.undo()              # and a run with no trace at all
    assert reader(metric).read(None, harness.Outcome(
        setup_s=0, attempted=1, failed=0, e2e={}, checks=[]), None) is None


def test_xspace_host_stats_and_device_lines_are_read():
    """A hand-written XSpace: the program's spans keep their stats, other
    host events are dropped, and the first chip's lines are read."""
    from jax.profiler import ProfileData
    txt = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 } }
      event_metadata { key: 1 value { id: 1
        name: "%matmul.1 = f32[8]{0} custom-call()" } }
      event_metadata { key: 2 value { id: 2 name: "jit_kset_matmul(1)" } } }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "main" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
        events { metadata_id: 2 offset_ps: 500000 duration_ps: 5000000
                 stats { metadata_id: 1 int64_value: 3 }
                 stats { metadata_id: 2 int64_value: 16 } }
        events { metadata_id: 3 offset_ps: 600000 duration_ps: 1000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } }
      event_metadata { key: 2 value { id: 2 name: "serve.step" } }
      event_metadata { key: 3 value { id: 3 name: "PjitFunction(fn)" } }
      stat_metadata { key: 1 value { id: 1 name: "step" } }
      stat_metadata { key: 2 value { id: 2 name: "active_rows" } } }
    """
    red = progtrace.Reduced(progtrace.load_xspace(
        ProfileData.from_text_proto(txt)))
    assert [(s.name, s.attrs) for s in red.spans] == [
        ("serve.step", {"step": 3, "active_rows": 16})]
    assert red.named("serve.step")[0].end - red.lo == pytest.approx(5500)
    runs = red.runs("jit_kset_")
    assert len(runs) == 1
    within = [(r.start, r.end) for r in runs]
    assert red.busy_ns(within) == pytest.approx(2000)
    assert red.kernel_ns(within) == pytest.approx(2000)


def mix_pad_share(traffic, vocab, seed, waves):
    """The padding share of the first waves, from the mix alone."""
    rows = padded = 0
    for w in range(waves):
        lens = [len(p) for p, _ in mixes.serve_wave(traffic, vocab, seed, w)]
        rows += len(lens) * max(lens)
        padded += len(lens) * max(lens) - sum(lens)
    return 100.0 * padded / rows


def test_serve_prefill_mix_padding():
    """The serve-prefill cell's waves, as the mix makes them: 46.875% of
    the prefill rows of waves 0-1 are padding, 41.67% of waves 0-2."""
    with open(os.path.join(tiny.BENCH, "traffic", "serve-prefill.json")) as f:
        t = json.load(f)
    vocab = 32000
    assert mix_pad_share(t, vocab, 2455000059, 2) == pytest.approx(46.875)
    assert mix_pad_share(t, vocab, 2455000059, 3) == pytest.approx(125 / 3)


def test_pad_share_of_an_engine_run_matches_the_mix(tmp_path, monkeypatch):
    """A tiny engine serves three waves of the tiny mix under the CPU
    profiler; the reader's share over its `serve.prefill` spans is the
    share the mix gives for those waves."""
    import jax
    from repro.models import build_model
    from repro.serve import Engine, Request

    serve = harness.load_module(os.path.join(tiny.BENCH, "drivers",
                                             "serve.py"), "drv_serve")
    t = tiny.CELLS["tiny.serve"][1]
    s = tiny.TINY_SIZES
    model = build_model(serve.program_config(s, {"arch": "h2o-danube-1.8b"}))
    engine = Engine(model, model.init(jax.random.PRNGKey(0)),
                    jax.make_mesh((1, 1), ("data", "model")),
                    max_len=t["max_len"], batch_slots=t["wave"])
    seed = 3000000019
    waves = 3
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for w in range(waves):
                engine.generate([Request(prompt=p, max_new_tokens=2) for p, _
                                 in mixes.serve_wave(t, s["vocab"], seed, w)])
    finally:
        jax.profiler.stop_trace()
    red = progtrace.reduce_dir(str(tmp_path))
    assert len(red.named("serve.prefill")) == waves
    assert not red.has_device          # the CPU records no TPU plane
    monkeypatch.setattr(progtrace, "for_outcome", lambda out: red)
    got = reader("serve.prefill.pad_share").read(None, None, None)
    assert got == pytest.approx(mix_pad_share(t, s["vocab"], seed, waves))
    assert 0 < got < 100
    assert reader("serve.decode.host_gap_ms").read(None, None, None) is None


RECORDED = os.path.join(tiny.BENCH, "testdata", "serve_trace.json")


def test_recorded_decode_steps_reduce_to_their_numbers(monkeypatch):
    """A few decode steps of danube-1.8b.serve-decode recorded on a TPU v5e:
    the readers give the numbers reduced on the chip, and a step's span is
    its decode program's device time, the sampler's small programs and the
    device's idle time."""
    with open(RECORDED) as f:
        data = json.load(f)
    t = progtrace.Trace.from_json(data["trace"])
    want = data["reduced"]
    device = read(monkeypatch, "serve.decode.step_device_ms", t)
    gap = read(monkeypatch, "serve.decode.host_gap_ms", t)
    assert device == pytest.approx(want["step_device_ms"], rel=1e-9)
    assert gap == pytest.approx(want["host_gap_ms"], rel=1e-9)
    steps = progtrace.Reduced(t).named("serve.step")
    assert len(steps) == want["steps"]
    assert all(s.attrs["active_rows"] > 0 for s in steps)
    mean_ms = 1e-6 * sum(s.end - s.start for s in steps) / len(steps)
    assert 0.95 * mean_ms < device + gap <= mean_ms
