"""Tests for the pluggable Strategy / CostModel interfaces.

* conformance suite every registered `CostModel` must pass (shapes, batched
  parity, training, clone isolation, save/load round-trip),
* strategy-registry behaviour (all five paper strategies registered, unknown
  names fail loudly, user classes plug in),
* the back-compat guarantee: string strategies resolved through the registry
  produce bit-identical `TuneResult`s to the frozen pre-refactor tuner
  (tests/_legacy_tuner.py) on a fixed seed, and string vs instance specs are
  equivalent through both `tune()` and `TuneSession.run()`.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _legacy_tuner import legacy_tune
from repro.autotune import devices as dev_mod
from repro.autotune.session import TuneSession
from repro.autotune.space import Workload
from repro.autotune.strategies import (STRATEGIES, STRATEGY_REGISTRY,
                                       MosesStrategy, RoundUpdate, Strategy,
                                       register_strategy, resolve_strategy,
                                       strategy_name)
from repro.autotune.tuner import TuneResult, tune
from repro.configs.moses import CostModelConfig, MosesConfig
from repro.core.cost_model import (COST_MODELS, CostModel, MLPCostModel,
                                   Records, ResidualMLPCostModel,
                                   batched_predict, normalize_per_task,
                                   predict, resolve_cost_model,
                                   train_cost_model)

# small config: parity holds for any hyperparameters, so shrink the loop
CM_CFG = CostModelConfig()
FAST_CFG = MosesConfig(online_epochs=3, adaptation_epochs=3,
                       population_size=32, evolution_rounds=2)

TASKS = [Workload("matmul", (256, 256, 128), name="a"),
         Workload("matmul", (256, 512, 128), name="b")]


def _synth_records(n=200, n_tasks=5, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, CM_CFG.feature_dim).astype(np.float32)
    raw = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
    g = (np.arange(n) % n_tasks).astype(np.int32)
    return Records(x=x, y=normalize_per_task(raw, g), g=g, raw_throughput=raw)


@pytest.fixture(scope="module")
def pretrained():
    src = _synth_records()
    model = MLPCostModel(CM_CFG)
    params = model.init(jax.random.PRNGKey(0))
    params, _ = model.train(params, src, epochs=2)
    return src, params


# ---------------------------------------------------------------------------
# CostModel conformance: every registered family must satisfy this contract
# ---------------------------------------------------------------------------


ALL_MODELS = sorted(COST_MODELS)


class TestCostModelConformance:
    @pytest.fixture(params=ALL_MODELS)
    def model(self, request):
        return resolve_cost_model(request.param, CM_CFG)

    def test_registered_and_named(self, model):
        assert isinstance(model, CostModel)
        assert COST_MODELS[model.name] is type(model)

    def test_init_and_predict_shapes(self, model):
        params = model.init(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).randn(9, CM_CFG.feature_dim)
        s = model.predict(params, x.astype(np.float32))
        assert s.shape == (9,)
        assert np.all(np.isfinite(s))

    @pytest.mark.parametrize("n", [1, 8, 9, 33, 130])
    def test_batched_predict_parity(self, model, n):
        """Bucket padding must be invisible: batched == exact, any length."""
        params = model.init(jax.random.PRNGKey(1))
        x = np.random.RandomState(n).randn(n, CM_CFG.feature_dim)
        x = x.astype(np.float32)
        np.testing.assert_allclose(model.batched_predict(params, x),
                                   model.predict(params, x), atol=1e-6)

    def test_empty_batch(self, model):
        params = model.init(jax.random.PRNGKey(0))
        out = model.batched_predict(
            params, np.zeros((0, CM_CFG.feature_dim), np.float32))
        assert out.shape == (0,)

    def test_forward_exposes_hidden(self, model):
        """The adversarial discriminator reads (scores, hidden)."""
        params = model.init(jax.random.PRNGKey(0))
        x = jnp.zeros((4, CM_CFG.feature_dim))
        s, h = model.forward(params, x, return_hidden=True)
        assert s.shape == (4,)
        assert h.shape == (4, model.hidden_dim)

    def test_train_reduces_loss(self, model):
        rec = _synth_records(seed=2)
        params = model.init(jax.random.PRNGKey(2))
        params, losses = model.train(params, rec, epochs=5)
        assert losses[-1] < losses[0]

    def test_clone_params_isolated(self, model):
        """Training a clone must never write through to the original."""
        params = model.init(jax.random.PRNGKey(3))
        before = jax.tree.map(np.asarray, params)
        clone = model.clone_params(params)
        clone, _ = model.train(clone, _synth_records(seed=3), epochs=1)
        for k in before:
            np.testing.assert_array_equal(before[k], np.asarray(params[k]))
        assert any(
            not np.array_equal(np.asarray(clone[k]), before[k])
            for k in before)

    def test_save_load_roundtrip(self, model, tmp_path):
        params = model.init(jax.random.PRNGKey(4))
        path = str(tmp_path / f"{model.name}.npz")
        model.save(params, path)
        loaded = model.load(path)
        x = np.random.RandomState(4).randn(6, CM_CFG.feature_dim)
        x = x.astype(np.float32)
        np.testing.assert_array_equal(model.predict(params, x),
                                      model.predict(loaded, x))


class TestMLPDelegation:
    def test_interface_matches_free_functions_bitwise(self):
        """MLPCostModel goes through the same jit cache as the legacy free
        functions — required for the string-strategy parity guarantee."""
        model = MLPCostModel(CM_CFG)
        params = model.init(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).randn(21, CM_CFG.feature_dim)
        x = x.astype(np.float32)
        np.testing.assert_array_equal(model.predict(params, x),
                                      predict(params, x))
        np.testing.assert_array_equal(model.batched_predict(params, x),
                                      batched_predict(params, x))
        rec = _synth_records(seed=5)
        p1, l1 = model.train(model.clone_params(params), rec, epochs=2)
        p2, l2 = train_cost_model(model.clone_params(params), rec, CM_CFG,
                                  epochs=2)
        assert l1 == l2
        for k in p1:
            np.testing.assert_array_equal(np.asarray(p1[k]),
                                          np.asarray(p2[k]))


# ---------------------------------------------------------------------------
# Strategy registry
# ---------------------------------------------------------------------------


class TestStrategyRegistry:
    def test_all_paper_strategies_registered(self):
        assert STRATEGIES == ("raw", "ansor-random", "tenset-pretrain",
                              "tenset-finetune", "moses")
        for name in STRATEGIES:
            s = resolve_strategy(name)
            assert isinstance(s, Strategy) and s.name == name

    def test_unknown_name_raises_with_choices(self):
        with pytest.raises(KeyError, match="moses"):
            resolve_strategy("no-such-strategy")
        with pytest.raises(KeyError, match="mlp"):
            resolve_cost_model("no-such-model")

    def test_instances_pass_through(self):
        inst = MosesStrategy()
        assert resolve_strategy(inst) is inst
        model = ResidualMLPCostModel(CM_CFG)
        assert resolve_cost_model(model) is model
        assert strategy_name(inst) == "moses" == strategy_name("moses")

    def test_missing_pretrained_fails_loudly(self):
        with pytest.raises(AssertionError, match="pretrained"):
            tune(TASKS[:1], "tpu_v5e", "moses", FAST_CFG, trials_per_task=8)

    def test_user_strategy_plugs_into_tune(self):
        """A new scheme is one registered class — no tuner changes. This one
        has no model at all (params stays None), exercising the random-score
        fallback path."""
        @register_strategy("test-random-search")
        class RandomSearchStrategy(Strategy):
            def on_round(self, builder, feats, round_idx):
                return RoundUpdate(0.0, False)

        try:
            r = tune(TASKS[:1], "tpu_v5e", "test-random-search", FAST_CFG,
                     trials_per_task=16, seed=0)
            assert r.strategy == "test-random-search"
            assert r.tasks[0].measurements == 16
            assert r.tasks[0].best_throughput > 0
        finally:
            del STRATEGY_REGISTRY["test-random-search"]

    def test_evolution_accepts_cost_model(self):
        """evolutionary_search(score_fn=None, cost_model=..., params=...)
        ranks through the interface — identical picks to an explicit
        score_fn over the same model."""
        from repro.autotune.evolution import evolutionary_search
        model = MLPCostModel(CM_CFG)
        params = model.init(jax.random.PRNGKey(0))
        a = evolutionary_search(TASKS[0], None, np.random.RandomState(5),
                                population=32, rounds=1, top_k=8,
                                cost_model=model, params=params)
        b = evolutionary_search(
            TASKS[0], lambda f: model.batched_predict(params, f),
            np.random.RandomState(5), population=32, rounds=1, top_k=8)
        assert [c.knobs for c in a] == [c.knobs for c in b]

    def test_residual_model_swaps_under_paper_strategies(self, pretrained):
        """The second model family runs the full loop — online training
        under ansor-random and lottery-ticket adaptation + AC under moses —
        proving strategies only touch the CostModel interface."""
        model = ResidualMLPCostModel(CM_CFG, width=64, depth=2)
        r = tune(TASKS[:1], "tpu_edge", "ansor-random", FAST_CFG,
                 trials_per_task=16, seed=1, cost_model=model)
        assert r.tasks[0].best_throughput > 0

        src = _synth_records(seed=7)
        params = model.init(jax.random.PRNGKey(7))
        params, _ = model.train(params, src, epochs=2)
        r = tune(TASKS[:1], "tpu_edge", "moses", FAST_CFG, trials_per_task=16,
                 pretrained_params=params, source_pool=src, seed=1,
                 cost_model=model)
        assert r.tasks[0].best_throughput > 0


# ---------------------------------------------------------------------------
# Back-compat: registry-resolved strings == the pre-refactor if/elif tuner
# ---------------------------------------------------------------------------


def _assert_results_identical(a: TuneResult, b: TuneResult):
    assert a.strategy == b.strategy and a.device == b.device
    assert a.total_search_seconds == b.total_search_seconds
    assert len(a.tasks) == len(b.tasks)
    for ta, tb in zip(a.tasks, b.tasks):
        assert ta.best_config.knobs == tb.best_config.knobs
        assert ta.best_throughput == tb.best_throughput
        assert ta.best_latency == tb.best_latency
        assert ta.measurements == tb.measurements
        assert ta.search_seconds == tb.search_seconds
        assert ta.trajectory == tb.trajectory


class TestLegacyParity:
    @pytest.mark.parametrize("strategy,cross_task", [
        *[pytest.param(s, False, id=s) for s in STRATEGIES],
        pytest.param("moses", True, id="moses-cross_task")])
    def test_string_strategy_bit_identical_to_legacy(self, strategy,
                                                     cross_task, pretrained):
        src, params = pretrained
        kwargs = dict(trials_per_task=16, seed=3, cross_task=cross_task)
        if strategy in ("tenset-pretrain", "tenset-finetune", "moses"):
            kwargs["pretrained_params"] = params
        if strategy == "moses":
            kwargs["source_pool"] = src
        old = legacy_tune(TASKS, "tpu_edge", strategy, FAST_CFG, **kwargs)
        new = tune(TASKS, "tpu_edge", strategy, FAST_CFG, **kwargs)
        _assert_results_identical(old, new)

    def test_instance_spec_matches_string_spec(self, pretrained):
        src, params = pretrained
        kwargs = dict(trials_per_task=16, pretrained_params=params,
                      source_pool=src, seed=4)
        by_name = tune(TASKS, "tpu_edge", "moses", FAST_CFG, **kwargs)
        by_inst = tune(TASKS, "tpu_edge", MosesStrategy(), FAST_CFG, **kwargs)
        _assert_results_identical(by_name, by_inst)

    def test_session_string_and_instance_agree(self, pretrained):
        src, params = pretrained
        session = TuneSession(moses_cfg=FAST_CFG, pretrained_params=params,
                              source_pool=src, seed=2, trials_per_task=16)
        by_name = session.run(TASKS[:1], "tpu_edge", "tenset-finetune")
        by_inst = session.run(
            TASKS[:1], "tpu_edge",
            resolve_strategy("tenset-finetune"))
        _assert_results_identical(by_name, by_inst)
        assert len(session.results) == 2

    def test_session_rejects_unknown_strategy(self):
        session = TuneSession(moses_cfg=FAST_CFG)
        with pytest.raises(KeyError):
            session.run(TASKS[:1], "tpu_edge", "nope")


def _hostile(cfg) -> bool:
    return sum(v for _, v in cfg.knobs) % 3 == 0


class TestTuneMeasurement:
    """tune() measures every trial through a MeasurementExecutor."""

    def test_failed_measurements_are_poisoned_not_raised(self, monkeypatch):
        measure = dev_mod.measure

        def crashing(wl, cfg, device, trial=0, noisy=True):
            if _hostile(cfg):
                raise RuntimeError("kernel crashed")
            return measure(wl, cfg, device, trial=trial, noisy=noisy)

        monkeypatch.setattr(dev_mod, "measure", crashing)
        r = tune(TASKS, "tpu_edge", "ansor-random", FAST_CFG,
                 trials_per_task=32, seed=3, model_update_cost=2.0)
        poisoned = [p for t in r.tasks for p in t.poisoned]
        assert poisoned
        for t in r.tasks:
            assert t.measurements == len(t.measured) > 0
            assert not any(_hostile(c) for c, _, _ in t.measured)
            assert all(_hostile(c) and "kernel crashed" in err
                       for c, _, err in t.poisoned)
            assert not _hostile(t.best_config)
            # a failed config is tried twice (one retry) and both attempts
            # occupied the board; each round that measured paid its update
            def cost(c, wl=t.workload):
                return dev_mod.measurement_seconds(wl, c, "tpu_edge")
            rounds = len({trial for _, _, trial in t.measured})
            assert t.search_seconds == pytest.approx(
                sum(cost(c) for c, _, _ in t.measured)
                + sum(2 * cost(c) for c, _, _ in t.poisoned)
                + rounds * 2.0)

    def test_executor_threads_end_with_the_call(self):
        before = set(threading.enumerate())
        r = tune(TASKS[:1], "tpu_edge", "ansor-random", FAST_CFG,
                 trials_per_task=16, seed=0)
        assert r.tasks[0].poisoned == []
        left = [t for t in threading.enumerate()
                if t not in before and t.name.startswith("measure-")]
        assert left == []
