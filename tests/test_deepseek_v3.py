"""DeepSeek-V3 on one chip's expert-parallel share, at a small size on the
CPU: the engine (prefill whole and in row groups, then decode through the
MLA latent cache) against the plain reference's full forward pass
(`bench/reference_mla_moe.py`), the published router against a rule
written out by hand, the held-expert layer's shares against the uncut
layer, dropless dispatch under fully skewed routing, and YaRN."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for _p in (BENCH, os.path.join(BENCH, "drivers")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference_mla_moe as ref_mm  # noqa: E402
import serve_moe  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import common  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.common import ParamBuilder  # noqa: E402

# small widths of the published shape: 16 routed experts in 4 groups,
# top-4 within the best 2 groups, 4 held (experts 4-7), a shared expert,
# 1 dense + 2 expert layers, YaRN as published
SIZES = ref_mm.sizes({
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "vocab_size": 300, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096},
    "rms_norm_eps": 1e-6,
    "deployment": {"routed_experts": 16, "first_expert": 4,
                   "param_dtype": "float32", "activation_dtype": "float32"},
})


@pytest.fixture(scope="module")
def weights():
    return ref_mm.make_weights(jax.random.PRNGKey(5), SIZES)


# ------------------------------------------------------------ the engine

@pytest.mark.parametrize("prefill_rows", [None, 2])
def test_engine_decode_matches_reference_forward(weights, prefill_rows):
    """Three requests served greedily, prefilled whole or in groups of 2
    rows: every logit row the sampler sees matches the reference's full
    forward pass at that position of the left-padded row and the served
    tokens."""
    from repro.serve import Engine, Request
    s = SIZES
    model = build_model(serve_moe.program_config(s))
    params = serve_moe.program_params(weights, s)
    engine = Engine(model, params, jax.make_mesh((1, 1), ("data", "model")),
                    max_len=32, batch_slots=3, prefill_rows=prefill_rows)
    seen = []
    sample = engine._sample

    def keep(logits, temps):
        seen.append(np.asarray(logits))
        return sample(logits, temps)

    engine._sample = keep
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(1, s["vocab"], n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((9, 6), (12, 4), (5, 6))]
    engine.generate(reqs)
    width = 12
    for i, r in enumerate(reqs):
        row = [0] * (width - len(r.prompt)) + [int(x) for x in r.prompt]
        seq = row + r.out_tokens[:-1]
        at = list(range(width - 1, width - 1 + len(r.out_tokens)))
        want = np.asarray(ref_mm.decoder_logits(weights, s, seq, at))
        got = np.stack([seen[n][i, : s["vocab"]]
                        for n in range(len(r.out_tokens))])
        scale = np.abs(want).max()
        assert np.abs(got - want).max() / scale < 2e-4
        assert r.out_tokens == [int(t) for t in want.argmax(-1)]


def test_prefill_in_row_groups_joins_the_whole_waves_state(weights):
    s = SIZES
    model = build_model(serve_moe.program_config(s))
    assert model.cfg.scan_layers       # the expert layers are one scanned group
    params = serve_moe.program_params(weights, s)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 10), 1, s["vocab"])
    whole, lw = model.prefill(params, {"tokens": toks}, max_len=16)
    parts = [model.prefill(params, {"tokens": toks[g:g + 2]}, max_len=16)
             for g in (0, 2)]
    joined = model.join_states([p for p, _ in parts])
    assert jax.tree.structure(joined) == jax.tree.structure(whole)
    for a, b in zip(jax.tree.leaves(joined), jax.tree.leaves(whole)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_allclose(np.concatenate([l for _, l in parts]),
                               np.asarray(lw), rtol=1e-5, atol=1e-5)


def test_engine_reports_the_experts_it_holds(weights):
    from repro.obs import metrics as obs_metrics
    from repro.serve import Engine, Request
    s = SIZES
    model = build_model(serve_moe.program_config(s))
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.push_registry(reg)
    try:
        Engine(model, serve_moe.program_params(weights, s),
               jax.make_mesh((1, 1), ("data", "model")), max_len=16,
               batch_slots=3, prefill_rows=2).generate(
            [Request(prompt=np.ones(4, np.int32), max_new_tokens=2)] * 3)
    finally:
        obs_metrics.pop_registry(reg)
    assert reg.snapshot()["gauges"]["serve.engine.experts_held"] == 4


def test_mla_prefill_computes_the_latents_once(monkeypatch):
    cfg = get_smoke_config("deepseek-v3-671b")
    b = ParamBuilder(jax.random.PRNGKey(0), "float32")
    attn.init_mla(b, cfg)
    calls = []
    real = attn.mla_latents

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(attn, "mla_latents", counted)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
    attn.mla_prefill(b.params, cfg, x, jnp.arange(6), cache_len=8)
    assert len(calls) == 1


# ---------------------------------------------------------------- router

def hand_router(scores, bias, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's MoEGate (noaux_tc), one token at a time."""
    T, E = scores.shape
    per = E // n_group
    weights, chosen = [], []
    for t in range(T):
        c = scores[t] + bias
        gs = [sorted(c[g * per:(g + 1) * per])[-2:] for g in range(n_group)]
        gs = [a + b for a, b in gs]
        keep = sorted(range(n_group), key=lambda g: -gs[g])[:topk_group]
        cand = [e for e in range(E) if e // per in keep]
        pick = sorted(cand, key=lambda e: -c[e])[:top_k]
        w = np.array([scores[t, e] for e in pick])
        order = np.argsort(pick)
        weights.append((w / w.sum() * scale)[order])
        chosen.append(sorted(pick))
    return weights, chosen


def _router_cfg(E=16, n_group=4, topk_group=2, top_k=4):
    cfg = get_smoke_config("deepseek-v3-671b")
    return cfg.replace(d_model=32, moe=dataclasses.replace(
        cfg.moe, num_experts=E, n_group=n_group, topk_group=topk_group,
        top_k=top_k, routed_scaling_factor=2.5))


def test_router_matches_the_published_rule():
    cfg = _router_cfg()
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k1, (24, 32))
    p = {"router": jax.random.normal(k2, (32, 16)) / math.sqrt(32),
         "router_bias": 0.3 * jax.random.normal(k3, (16,))}
    w, idx, aux = moe_mod._router(p, cfg, x)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    hw, hidx = hand_router(scores, np.asarray(p["router_bias"]), 4, 2, 4, 2.5)
    assert float(aux) == 0.0
    for t in range(24):
        got = dict(zip(np.asarray(idx[t]).tolist(), np.asarray(w[t])))
        assert sorted(got) == hidx[t]
        want = dict(zip(hidx[t], hw[t]))
        np.testing.assert_allclose([got[e] for e in hidx[t]],
                                   [want[e] for e in hidx[t]], rtol=1e-5)
        np.testing.assert_allclose(float(w[t].sum()), 2.5, rtol=1e-5)


def test_router_excludes_experts_outside_the_best_groups():
    """The single best expert sits in a group whose other experts are
    weak; the groups ranked by their two best scores leave it out."""
    cfg = _router_cfg(E=8, n_group=4, topk_group=2, top_k=2)
    logits = np.full((1, 8), -4.0, np.float32)
    logits[0, 0] = 5.0                 # group 0: 5.0 and -4.0
    logits[0, 2:4] = 2.0               # group 1: 2.0 and 2.0
    logits[0, 4:6] = 1.5               # group 2: 1.5 and 1.5
    p = {"router": jnp.eye(8, dtype=jnp.float32),
         "router_bias": jnp.zeros(8)}
    w, idx, _ = moe_mod._router(p, cfg.replace(d_model=8), jnp.asarray(logits))
    assert sorted(np.asarray(idx[0]).tolist()) == [2, 3]
    np.testing.assert_allclose(np.asarray(w[0]), [1.25, 1.25], rtol=1e-6)
    # the bias moves the choice, the weights stay the unbiased scores
    p["router_bias"] = jnp.zeros(8).at[4].set(1.0)
    w, idx, _ = moe_mod._router(p, cfg.replace(d_model=8), jnp.asarray(logits))
    assert sorted(np.asarray(idx[0]).tolist()) == [2, 4]
    s = 1 / (1 + math.exp(-2.0)), 1 / (1 + math.exp(-1.5))
    np.testing.assert_allclose(sorted(np.asarray(w[0]).tolist()),
                               sorted([2.5 * s[0] / sum(s),
                                       2.5 * s[1] / sum(s)]), rtol=1e-6)


# ------------------------------------------------------ held-expert layer

def _moe_cfg(held, first):
    cfg = get_smoke_config("deepseek-v3-671b").replace(
        activation_dtype="float32", d_model=64)
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=16, top_k=4, n_group=4, topk_group=2,
        d_ff_expert=32, d_ff_shared=32, experts_held=held,
        first_expert=first))


def _uncut_params(key):
    """One expert layer with all 16 experts (the reference's layout)."""
    cfg = _moe_cfg(0, 0)
    b = ParamBuilder(key, "float32")
    moe_mod.init_moe(b, cfg)
    p = dict(b.params["moe"])
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 1),
                                               (16,))
    return cfg, p


def _share(p, first, held):
    return {**p, **{n: p[n][first:first + held] for n in ("wi", "wg", "wo")}}


def test_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares of 4 experts each, the partial outputs with the
    shared expert counted once add up to the uncut reference layer."""
    cfg, p = _uncut_params(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 9, 64))
    shared = moe_mod._shared_ffn(p, cfg, x.reshape(18, 64)).reshape(x.shape)
    total = sum(moe_mod.moe_forward(_share(p, f, 4), _moe_cfg(4, f), x)[0]
                - shared for f in (0, 4, 8, 12)) + shared
    s = dict(SIZES, d_model=64, experts=16, experts_held=16, first_expert=0,
             d_ff_expert=32)
    w = {"router": p["router"], "router_bias": p["router_bias"],
         "wi": p["wi"], "wg": p["wg"], "wo_mlp": p["wo"],
         "shared_wi": p["shared_wi"], "shared_wg": p["shared_wg"],
         "shared_wo": p["shared_wo"]}
    want = ref_mm.held_moe(x.reshape(18, 64), w, s, low=False)
    np.testing.assert_allclose(np.asarray(total).reshape(18, 64),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


def test_no_token_is_dropped_when_all_route_to_one_held_expert():
    """A correction bias sends every token to expert 5, held here: all 64
    assignments are computed (capacity is the tokens in the call), where a
    capacity dispatch of the usual factor would drop most of them."""
    cfg, p = _uncut_params(jax.random.PRNGKey(9))
    p["router_bias"] = jnp.zeros(16).at[5].set(100.0)
    held = _moe_cfg(4, 4)
    ps = _share(p, 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 16, 64))
    y, _ = moe_mod.moe_forward(ps, held, x)
    oracle, _ = moe_mod.moe_forward(ps, held, x, impl="dense_mask")
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle), rtol=2e-4,
                               atol=2e-5)
    w, idx, _ = moe_mod._router(p, held, x.reshape(64, 64))
    assert bool((idx == 5).any(-1).all())
    C = math.ceil(64 * 4 * 1.25 / 16)      # the capacity dispatch's
    dropped = moe_mod.held_experts_ffn(held, x.reshape(64, 64), w, idx,
                                       ps["wi"], ps["wg"], ps["wo"], 4, C)
    full = moe_mod.held_experts_ffn(held, x.reshape(64, 64), w, idx,
                                    ps["wi"], ps["wg"], ps["wo"], 4, 64)
    assert float(jnp.abs(full - dropped).max()) > 0.1 * float(
        jnp.abs(full).max())


# ------------------------------------------------------------------- YaRN

def test_yarn_frequencies_and_scale_match_the_formula():
    rs = get_config("deepseek-v3-671b").rope_scaling
    D, theta = 64, 10000.0
    # correction dims: floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) = 10 and
    # ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23
    assert common.yarn_correction_range(rs, D, theta) == (10, 23)
    base = 1.0 / theta ** (np.arange(0, D, 2) / D)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = np.asarray(common.rope_freqs(D, theta, rs))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[:10] == pytest.approx(base[:10], rel=1e-6)     # kept
    assert got[23:] == pytest.approx(base[23:] / 40, rel=1e-6)  # scaled
    cfg = get_config("deepseek-v3-671b")
    assert attn.mla_scale(cfg) == pytest.approx(
        (0.1 * math.log(40) + 1) ** 2 / math.sqrt(192), rel=1e-12)
    assert (0.1 * math.log(40) + 1) ** 2 == pytest.approx(1.874, abs=1e-3)
    np.testing.assert_allclose(np.asarray(ref_mm.yarn_inv_freq(
        dict(SIZES, qk_rope=64))), want, rtol=1e-6)
