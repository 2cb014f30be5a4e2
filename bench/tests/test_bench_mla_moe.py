"""The `serve_moe` driver and the DeepSeek-V3 cell's yardstick on the CPU:
a sound tiny run is correct; a run whose program routes by softmax or
leaves out the shared expert is not; the float8 control fails the cell's
limit; the counts of a decode step add up by hand; and the scope shares
read a hand-made trace and two decode steps recorded on a TPU v5e."""
import json
import math
import os

import numpy as np
import pytest

import tiny
import tiny_mla_moe
import counts_mla_moe
import harness
import hloscope
import progtrace
import reference_mla_moe as ref_mm

CONFIG = os.path.join(tiny.BENCH, "configs", "deepseek-v3-ep32.json")
CELL = "dsv3-ep32.serve-decode"


def limit(name):
    with open(os.path.join(tiny.BENCH, "limits", CELL + ".json")) as f:
        return json.load(f)[name]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_mla_moe.make_root(str(tmp_path_factory.mktemp("bench")))


def test_sound_run_is_correct(root, capsys):
    line = tiny.run_cell(root, tiny_mla_moe.CELL, capsys=capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "decode_tok_s", "tpot_p95_ms"}
    assert line["attempted"] % tiny_mla_moe.TRAFFIC["wave"] == 0


def _softmax_router(p, cfg, x):
    """DBRX's rule in place of the published one."""
    import jax
    import jax.numpy as jnp
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)
    return w / w.sum(-1, keepdims=True), idx, jnp.zeros(())


def _no_shared(p, cfg, x):
    import jax.numpy as jnp
    return jnp.zeros_like(x)


@pytest.mark.parametrize("fault,fn", [("_router", _softmax_router),
                                      ("_shared_ffn", _no_shared)],
                         ids=["softmax_routing", "shared_expert_left_out"])
def test_planted_fault_is_not_correct(root, capsys, monkeypatch, fault, fn):
    from repro.models import moe
    monkeypatch.setattr(moe, fault, fn)
    line = tiny.run_cell(root, tiny_mla_moe.CELL, capsys=capsys)
    assert line["correct"] is False
    gap = line["checks"]["served_logit_gap_mean"]
    assert gap["value"] > gap["limit"]


# sizes at which the float8 control's rounding shows, small enough for
# the CPU: the published shape at a quarter to a sixteenth of its widths
CONTROL = dict(ref_mm.sizes(json.load(open(CONFIG))), layers=3,
               dense_layers=1, d_model=512, heads=8, q_lora=128, kv_lora=64,
               qk_nope=32, qk_rope=16, v_head=32, d_ff=1024, d_ff_expert=128,
               experts=64, experts_held=8, vocab=4096)


def test_float8_control_fails_served_gap_limit():
    import jax
    s = CONTROL
    w = jax.jit(lambda k: ref_mm.make_weights(k, s))(jax.random.PRNGKey(11))
    rng = np.random.default_rng(11)
    row = list(rng.integers(1, s["vocab"], 96))
    served = list(rng.integers(1, s["vocab"], 160))
    gaps = ref_mm.served_gaps(w, s, row, served, low=True)
    assert gaps.mean() > limit("served_logit_gap_mean")


# ----------------------------------------------------------------- counts

def test_weights_count_the_programs_parameters():
    """The counts' parts add up to every parameter of the program's tree at
    the cell's sizes (its vocabulary padded to 128 rows as the program's)."""
    import jax
    from repro.models import build_model
    serve_moe = harness.load_module(os.path.join(
        tiny.BENCH, "drivers", "serve_moe.py"), "drv_serve_moe")
    s = ref_mm.sizes(json.load(open(CONFIG)))
    model = build_model(serve_moe.program_config(s))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
    padded = dict(s, vocab=ref_mm.padded_vocab(s))
    assert sum(counts_mla_moe.weights(padded).values()) == n


def test_decode_step_counts_by_hand():
    """One row at context 1000 of the cell, counted by hand: MLA's seven
    matrices (absorbed decode multiplies by each once), 278,528 FLOPs per
    cached position and layer, the dense MLP, the router's 256 columns,
    the held experts' expected quarter of an assignment, the shared expert
    and the 16160-column head."""
    s = ref_mm.sizes(json.load(open(CONFIG)))
    mla = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 128
           + 512 * 128 * 128 + 128 * 128 * 7168)
    assert counts_mla_moe.mla_weights(s) == mla == 187_105_280
    per_row = (2 * 5 * mla + 2 * 3 * 7168 * 18432 + 2 * 4 * 7168 * 256
               + 2 * 4 * (8 * 8 / 256) * 3 * 7168 * 2048
               + 2 * 4 * 3 * 7168 * 2048 + 2 * 7168 * 16160)
    attn = 5 * 2 * 128 * (2 * 512 + 64) * 1000
    assert 2 * 128 * (2 * 512 + 64) == 278_528
    flops, nbytes = counts_mla_moe.decode_step(s, [1000], 2)
    assert flops == pytest.approx(per_row + attn, rel=1e-12)
    weights = (5 * mla + 3 * 7168 * 18432 + 4 * 7168 * 256
               + 4 * 8 * 3 * 7168 * 2048 + 4 * 3 * 7168 * 2048
               + 7168 * 16160 + 7168)                   # head, one embed row
    f32 = 4 * 256 + 5 * (2 * 7168 + 1536 + 512) + 7168  # bias, norms
    cache = 5 * 1152 * 1001
    assert nbytes == 2 * weights + 4 * f32 + cache
    # about 6.1 GB a step, memory-bound on a v5e at 128 rows
    flops, nbytes = counts_mla_moe.decode_step(s, [768] * 128, 2)
    peaks = harness.load_peaks()["TPU v5 lite"]
    assert nbytes / peaks["hbm_bytes_per_s"] > flops / peaks[
        "bf16_flops_per_s"]
    assert 6.0e9 < nbytes < 7.0e9


# ----------------------------------------------------------- scope shares

def test_hlo_text_gives_each_instruction_its_scope():
    text = """
ENTRY %main.9 (p: bf16[8]) -> bf16[8] {
  %fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop, calls=%f.1, metadata={op_name="jit(serve_decode)/while/body/mla/dot_general" source_file="a.py" source_line=3}
  %copy.1 = bf16[8]{0} copy(bf16[8]{0} %fusion.3)
  ROOT %fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %copy.1), kind=kLoop, calls=%f.2, metadata={op_name="jit(serve_decode)/while/body/moe.experts/mul"}
}"""
    scopes = hloscope.op_scopes(text)
    assert scopes == {
        "fusion.3": "jit(serve_decode)/while/body/mla/dot_general",
        "fusion.4": "jit(serve_decode)/while/body/moe.experts/mul"}
    assert hloscope.instruction("%fusion.3 = bf16[8]{0} fusion(x)") == \
        "fusion.3"
    assert hloscope.instruction("fusion.3 bf16[8]") == "fusion.3"
    assert hloscope.under(scopes["fusion.4"], "moe")
    assert not hloscope.under(scopes["fusion.4"], "mla")
    assert not hloscope.under("jit(f)/mlank/x", "mla")


def _read(monkeypatch, metric, trace, scopes):
    red = progtrace.Reduced(trace)
    monkeypatch.setattr(progtrace, "for_outcome", lambda out: red)
    mod = harness.load_module(os.path.join(tiny.BENCH, "metrics",
                                           metric + ".py"),
                              "m_" + metric.replace(".", "_"))
    out = harness.Outcome(setup_s=0, attempted=1, failed=0, e2e={},
                          checks=[], trace_dir="unused",
                          counts={"decode_op_scopes": scopes})
    return mod.read(None, out, None)


def test_shares_of_a_hand_made_trace(monkeypatch):
    """Two runs of the decode program (busy 10 + 10 ns, with an overlap)
    and a sampler program between them: MLA ops take 6 ns of them, expert
    ops 9 (one overlaps an MLA op), an unscoped copy the rest; the sampler's op is not counted."""
    trace = progtrace.Trace.from_json({
        "spans": [["bench.window", 0, 100, {}]],
        "ops": [["%fusion.1 = bf16[8]{0} fusion(a)", 10, 14],   # mla
                ["%fusion.2 = bf16[8]{0} fusion(b)", 13, 18],   # moe, overlaps
                ["%copy.1 = bf16[8]{0} copy(c)", 18, 20],
                ["%fusion.2 = bf16[8]{0} fusion(b)", 30, 31],   # sampler's
                ["%fusion.1 = bf16[8]{0} fusion(a)", 50, 52],
                ["%fusion.3 = bf16[8]{0} fusion(d)", 52, 56],   # moe
                ["%copy.1 = bf16[8]{0} copy(c)", 56, 60]],
        "modules": [["jit_serve_decode(1)", 10, 20], ["jit_argmax(2)", 30, 31],
                    ["jit_serve_decode(1)", 50, 60]]})
    scopes = {"fusion.1": "jit(serve_decode)/mla/dot_general",
              "fusion.2": "jit(serve_decode)/moe.router/sigmoid",
              "fusion.3": "jit(serve_decode)/moe.experts/dot_general"}
    assert _read(monkeypatch, "serve.decode.mla_share", trace, scopes) == \
        pytest.approx(100 * 6 / 20)
    assert _read(monkeypatch, "serve.decode.moe_share", trace, scopes) == \
        pytest.approx(100 * 9 / 20)
    assert _read(monkeypatch, "serve.decode.moe_share", trace, {}) is None


RECORDED = os.path.join(tiny.BENCH, "testdata", "dsv3_decode_trace.json")


def test_recorded_decode_steps_reduce_to_their_numbers(monkeypatch):
    with open(RECORDED) as f:
        data = json.load(f)
    t = progtrace.Trace.from_json(data["trace"])
    want = data["reduced"]
    moe = _read(monkeypatch, "serve.decode.moe_share", t, data["scopes"])
    mla = _read(monkeypatch, "serve.decode.mla_share", t, data["scopes"])
    assert moe == pytest.approx(want["moe_share"], rel=1e-9)
    assert mla == pytest.approx(want["mla_share"], rel=1e-9)
    assert 0 < moe and 0 < mla and moe + mla <= 100
    # again by marking a 10 ns timeline of the decode runs' leaf ops
    red = progtrace.Reduced(t)
    lo = red.lo
    line = np.zeros(int((red.hi - lo) / 10) + 2, np.int8)
    for r in red.runs("jit_serve_decode"):
        line[int((r.start - lo) / 10):int((r.end - lo) / 10)] |= 1
    busy = np.zeros_like(line)
    moe_line = np.zeros_like(line)
    import devtrace
    for e in devtrace.leaves(red.ops):
        a, b = int((e.start - lo) / 10), int((e.end - lo) / 10)
        busy[a:b] = 1
        if hloscope.under(data["scopes"].get(hloscope.instruction(e.name),
                                             ""), "moe"):
            moe_line[a:b] = 1
    est = 100 * (moe_line & line).sum() / (busy & line).sum()
    assert est == pytest.approx(moe, abs=2.0)
