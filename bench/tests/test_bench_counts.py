"""The benchmark's operation and byte counts against hand-worked totals."""
import json
import os

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import counts
import mixes

BENCH = tiny.BENCH


def sizes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def totals(cfg, mix, kind, what):
    calls = mixes.kset_calls(sizes(cfg), traffic(mix))
    total = 0.0
    for c in calls:
        if c["kind"] != kind:
            continue
        if kind == "matmul":
            f, b = counts.matmul(*c["dims"], out_bytes=counts.F32)
        else:
            f, b = counts.attention(*c["dims"])
        total += (f if what == "flops" else b) * c["count"]
    return total


def test_danube_prefill_kernel_set_flops():
    # 24 x (qkv + out + ffn_in + ffn_out) + the head: 27.3 TFLOP
    assert totals("h2o-danube-1.8b", "kset-prefill", "matmul", "flops") == \
        pytest.approx(27.31e12, rel=2e-3)
    # causal attention over 4 x 32 heads x 2048 x 80, x 24 layers: 2.1 TFLOP
    assert totals("h2o-danube-1.8b", "kset-prefill", "attention",
                  "flops") == pytest.approx(2.063e12, rel=2e-3)


def test_glm_decode_kernel_set():
    calls = mixes.kset_calls(sizes("glm4-9b"), traffic("kset-decode"))
    weights = sum(2.0 * c["dims"][1] * c["dims"][2] * c["count"]
                  for c in calls)
    assert weights == pytest.approx(17.56e9, rel=2e-3)       # 17.6 GB
    assert totals("glm4-9b", "kset-decode", "matmul", "flops") == \
        pytest.approx(1.124e12, rel=2e-3)                      # 1.1 TFLOP
    assert all(c["kind"] == "matmul" for c in calls)


def test_pass_order_repeats_layers():
    calls = mixes.kset_calls(sizes("h2o-danube-1.8b"),
                             traffic("kset-prefill"))
    order = mixes.pass_order(calls)
    assert len(order) == 24 * 5 + 1
    assert [c["name"] for c in order[:6]] == ["qkv", "attn", "out", "ffn_in",
                                             "ffn_out", "qkv"]
    assert order[-1]["name"] == "lm_head"


@pytest.mark.parametrize("S,window", [(1, 0), (7, 0), (64, 16), (64, 64),
                                      (100, 128), (33, 1)])
def test_causal_pairs_brute_force(S, window):
    want = sum(1 for q in range(S) for k in range(S)
               if k <= q and (window <= 0 or k > q - window))
    assert counts.causal_pairs(S, window) == want


def test_roofline_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000.0, 10.0, peaks) == 10.0
    assert counts.least_seconds(10.0, 1000.0, peaks) == 100.0


def test_model_sizes_match_published_parameter_count():
    s = sizes("h2o-danube-1.8b")
    assert counts.model_weights(s) == pytest.approx(1.83e9, rel=1e-2)


def test_decode_step_counts():
    s = sizes("h2o-danube-1.8b")
    f, b = counts.decode_step(s, [100, 5000], param_bytes=4)
    # two rows: every weight once (f32), K/V of 100 and 4096 positions
    kv = 2 * 2 * 8 * 80 * 24
    assert b == pytest.approx(
        4 * (counts.model_weights(s) - 32000 * 2560 + 2 * 2560)
        + kv * (100 + 4096 + 2))
    assert f > 2 * 2 * 24 * counts.layer_weights(s)


def test_prefill_flops_counts_real_tokens_only():
    s = sizes("h2o-danube-1.8b")
    assert counts.prefill_flops(s, 2048) < counts.prefill_flops(s, 4096) / 2 \
        + 2 * 2560 * 32000
