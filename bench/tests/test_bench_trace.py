"""The trace reduction: busy time as the union of device intervals, kernel
time by program name, idle gaps attributed to the bench's spans, and the
roofline and mfu readers, on a small trace recorded on a TPU v5e and on
hand-made ones."""
import json
import os

import pytest

import tiny
import devtrace
import harness

RECORDED = os.path.join(tiny.BENCH, "testdata", "kset_trace.json")


def ev(name, a, b):
    return devtrace.Ev(name, float(a), float(b))


def hand_trace():
    ops = [ev("%tpu_custom_call.1 = bf16[8,128]{1,0} custom-call(x)", 10, 30),
           ev("%copy.1 = f32[8]{0} copy(y)", 25, 40),          # overlaps
           ev("%tpu_custom_call.1 = bf16[8,128]{1,0} custom-call(z)", 60, 70),
           ev("%fusion.2 = f32[8]{0} fusion(w)", 80, 95)]
    mods = [ev("jit_kset_matmul(123)", 10, 41),
            ev("jit_kset_attention(456)", 60, 70),
            ev("jit_other(789)", 80, 95)]
    spans = [ev("bench.window", 0, 100), ev("bench.pass", 6, 98),
             ev("bench.call.qkv", 42, 58), ev("bench.call.attn", 71, 79)]
    return devtrace.Trace({"/device:TPU:0": devtrace.Device(ops, mods)},
                          spans)


def test_busy_is_the_union_of_op_intervals():
    red = devtrace.Reduced(hand_trace())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx((40 - 10 + 70 - 60 + 95 - 80) * 1e-9)
    assert red.idle_share == pytest.approx(1 - 55 / 100)


def test_kernel_time_by_program_name():
    red = devtrace.Reduced(hand_trace())
    assert red.kernel_seconds("jit_kset_matmul") == pytest.approx(20e-9)
    assert red.kernel_seconds("jit_kset_attention") == pytest.approx(10e-9)
    assert red.kernel_seconds("jit_none") == 0.0


def test_idle_gaps_go_to_the_innermost_span():
    red = devtrace.Reduced(hand_trace())
    assert red.gaps() == [(0, 10), (40, 60), (70, 80), (95, 100)]
    got = dict(red.breakdown()["idle_gaps"])
    assert got["bench.call.qkv"] == pytest.approx(20e-9)
    assert got["bench.call.attn"] == pytest.approx(10e-9)
    assert got[devtrace.OUTSIDE] == pytest.approx(10e-9)    # gap at 0..10
    assert got["bench.pass"] == pytest.approx(5e-9)         # gap at 95..100
    ops = dict(red.breakdown()["device_ops"])
    assert ops["jit_kset_matmul:tpu_custom_call.1 bf16[8,128]"] == \
        pytest.approx(20e-9)


def test_breakdown_sums_leaf_ops_only():
    """A `while` op spans the ops of its body, which are listed too; an op
    that only overlaps another is a leaf."""
    ops = [ev("%while.1 = s32[] while(x)", 10, 50),
           ev("%fusion.1 = f32[8]{0} fusion(a)", 12, 20),
           ev("%fusion.2 = f32[8]{0} fusion(b)", 25, 50),
           ev("%copy.1 = f32[8]{0} copy(c)", 45, 60)]
    t = devtrace.Trace({"/device:TPU:0": devtrace.Device(
        ops, [ev("jit_step(1)", 10, 60)])}, [ev("bench.window", 0, 100)])
    got = dict(devtrace.Reduced(t).breakdown()["device_ops"])
    assert set(got) == {"jit_step:fusion.1 f32[8]", "jit_step:fusion.2 f32[8]",
                        "jit_step:copy.1 f32[8]"}
    assert got["jit_step:fusion.2 f32[8]"] == pytest.approx(25e-9)


def test_window_clips_events():
    t = hand_trace()
    t.spans[0] = ev("bench.window", 20, 65)
    red = devtrace.Reduced(t)
    assert red.busy_s == pytest.approx((40 - 20 + 65 - 60) * 1e-9)


def test_xspace_planes_are_read():
    """A hand-written XSpace, as the profiler writes it, parses to the same
    events."""
    from jax.profiler import ProfileData
    txt = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 4000000 duration_ps: 5000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 5000000 duration_ps: 2000000 } }
      event_metadata { key: 1 value { id: 1
        name: "%tpu_custom_call.1 = f32[8]{0} custom-call()" } }
      event_metadata { key: 2 value { id: 2 name: "jit_kset_matmul(1)" } } }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "main" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
      event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
    """
    t = devtrace.load_xspace(ProfileData.from_text_proto(txt))
    red = devtrace.Reduced(t)
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_s == pytest.approx(2e-6)
    assert red.kernel_seconds("jit_kset_matmul") == pytest.approx(2e-6)


def test_no_window_span_is_an_error():
    t = hand_trace()
    t.spans = t.spans[1:]
    with pytest.raises(ValueError):
        devtrace.Reduced(t)


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        data = json.load(f)
    return data, devtrace.Reduced(devtrace.Trace.from_json(data["trace"]))


def test_recorded_trace_reduces_to_its_numbers(recorded):
    data, red = recorded
    want = data["reduced"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red.busy_s <= red.window_s
    for prefix, secs in want["kernel_seconds"].items():
        assert red.kernel_seconds(prefix) == pytest.approx(secs, rel=1e-9)
    # busy time again, by marking a 100 ns timeline (another algorithm)
    import numpy as np
    step = 100.0
    line = np.zeros(int((red.hi - red.lo) / step) + 1, bool)
    for op in next(iter(red.devices.values())).ops:
        line[int((op.start - red.lo) / step):int((op.end - red.lo) / step)] = True
    n_ops = len(next(iter(red.devices.values())).ops)
    assert abs(line.sum() * step * 1e-9 - red.busy_s) < 2 * n_ops * step * 1e-9


@pytest.mark.parametrize("metric", ["kset.matmul_roofline",
                                    "kset.attention_roofline", "kset.mfu",
                                    "device.idle.kset"])
def test_readers_on_the_recorded_trace(recorded, metric):
    data, red = recorded
    mod = harness.load_module(
        os.path.join(tiny.BENCH, "metrics", metric + ".py"), "m_" +
        metric.replace(".", "_"))

    class Ctx:
        device = {"peaks": harness.load_peaks()[data["device_kind"]]}

    out = harness.Outcome(setup_s=0, attempted=1,
                          failed=0, e2e=data["e2e"], checks=[],
                          counts=data["counts"])
    v = mod.read(Ctx, out, red)
    assert v == pytest.approx(data["metrics"][metric], rel=1e-9)
    assert 0 < v <= 100
