"""The correctness check of each driver, run on the CPU at a small size:
sound runs pass, and a run whose timed path is broken underneath (an answer
or a token altered where it is produced, half of the rows or of the wave
left out, a decode step that returns its state unchanged) comes out not
correct. The float8 control of the references fails the limits the cells
hold."""
import json
import os

import numpy as np
import pytest

import tiny
import reference as ref

LIMITS = os.path.join(tiny.BENCH, "limits")


def limit(cell, name):
    with open(os.path.join(LIMITS, cell + ".json")) as f:
        return json.load(f)[name]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_kset_sound_run_is_correct(root, capsys):
    line = tiny.run_cell(root, "tiny.kset-prefill", capsys=capsys)
    assert line["correct"] is True
    assert set(line["checks"]) == {"matmul_rel_err", "attention_rel_err"}
    assert line["metrics"]["kset_ms"]["value"] > 0


def test_kset_traced_run_reads_the_trace(root, capsys, monkeypatch):
    """On the CPU the profiler records no TPU plane: the traced run's
    reduction has nothing to read and says so."""
    with pytest.raises(ValueError, match="TPU"):
        tiny.run_cell(root, "tiny.kset-prefill", trace=1, capsys=capsys)


def _alter_matmul(monkeypatch, how):
    from repro.kernels import ops
    real = ops.tuned_matmul

    def broken(a, b, **kw):
        out = real(a, b, **kw)
        if how == "answer":
            return out.at[0, 0].add(0.25 * abs(out).max().astype(out.dtype))
        return out.at[out.shape[0] // 2:].set(0)     # half the rows left out

    monkeypatch.setattr(ops, "tuned_matmul", broken)


@pytest.mark.parametrize("how", ["answer", "half_rows"])
def test_kset_broken_matmul_is_not_correct(root, capsys, monkeypatch, how):
    _alter_matmul(monkeypatch, how)
    line = tiny.run_cell(root, "tiny.kset-prefill", capsys=capsys)
    assert line["correct"] is False
    assert line["checks"]["matmul_rel_err"]["value"] > \
        line["checks"]["matmul_rel_err"]["limit"]


def test_kset_broken_attention_is_not_correct(root, capsys, monkeypatch):
    from repro.kernels import ops
    real = ops.tuned_flash_attention

    def broken(q, k, v, **kw):
        out = real(q, k, v, **kw)
        return out.at[0, -1].multiply(-1.0)

    monkeypatch.setattr(ops, "tuned_flash_attention", broken)
    line = tiny.run_cell(root, "tiny.kset-prefill", capsys=capsys)
    assert line["correct"] is False


def test_serve_sound_run_is_correct(root, capsys):
    line = tiny.run_cell(root, "tiny.serve", capsys=capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] % 2 == 0


def test_serve_altered_token_is_not_correct(root, capsys, monkeypatch):
    from repro.serve.engine import Engine
    real = Engine._sample
    calls = {"n": 0}

    def broken(self, logits, temps):
        out = real(self, logits, temps)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % logits.shape[-1]
        return out

    monkeypatch.setattr(Engine, "_sample", broken)
    line = tiny.run_cell(root, "tiny.serve", capsys=capsys)
    assert line["correct"] is False


def test_serve_half_the_wave_left_out_is_not_correct(root, capsys,
                                                     monkeypatch):
    from repro.serve.engine import Engine
    real = Engine.generate

    def half(self, requests):
        real(self, requests[:len(requests) // 2])
        return list(requests)

    monkeypatch.setattr(Engine, "generate", half)
    line = tiny.run_cell(root, "tiny.serve", capsys=capsys)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] // 2


def test_serve_step_returning_its_state_is_not_correct(root, capsys,
                                                       monkeypatch):
    from repro.serve import engine as eng
    real = eng.make_serve_step

    def stuck(model, mesh, **kw):
        step = real(model, mesh, **kw)
        return lambda p, state, tok: (state, step(p, state, tok)[1])

    monkeypatch.setattr(eng, "make_serve_step", stuck)
    line = tiny.run_cell(root, "tiny.serve", capsys=capsys)
    assert line["correct"] is False


# ------------------------------------------------------- float8 control

def _normal(key, shape):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("cell,dims", [
    ("danube-1.8b.kset-prefill", (512, 768, 640)),
    ("glm4-9b.kset-decode", (64, 512, 1024))])
def test_float8_control_fails_matmul_limit(cell, dims):
    import jax
    M, N, K = dims
    a = _normal(jax.random.PRNGKey(1), (M, K))
    b = _normal(jax.random.PRNGKey(2), (K, N))
    err = ref.matmul_err(None, a, b, low=True, rows=M)
    assert err > limit(cell, "matmul_rel_err")


def test_float8_control_fails_attention_limit():
    import jax
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (_normal(kk, (4, 256, 80)) for kk in ks)
    err = ref.attention_err(None, q, k, v, window=0, low=True)
    assert err > limit("danube-1.8b.kset-prefill", "attention_rel_err")


CONTROL_SIZES = dict(tiny.TINY_SIZES, layers=6, d_model=512, heads=4,
                     kv_heads=2, head_dim=64, d_ff=1024, vocab=8192,
                     window=64)


@pytest.mark.parametrize("cell", ["danube-1.8b.serve-decode",
                                  "danube-1.8b.serve-prefill"])
def test_float8_control_fails_served_gap_limit(cell):
    import jax
    s = CONTROL_SIZES
    w = jax.jit(lambda k: ref.make_weights(k, s))(jax.random.PRNGKey(11))
    rng = np.random.default_rng(11)
    row = list(rng.integers(1, s["vocab"], 96))
    served = list(rng.integers(1, s["vocab"], 160))
    gaps = ref.served_gaps(w, s, row, served, low=True)
    assert gaps.max() > limit(cell, "served_logit_gap")
