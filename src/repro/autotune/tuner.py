"""The auto-tuning loop (paper Fig. 2 pipeline + §3.6).

One loop serves every caller. `TaskTuner` is one (device, workload) job:
each `step()` runs one evolutionary-search + measure + model-update round,
and `finish()` runs the prediction-only phase and assembles the
`TaskResult`. `tune()` walks a task list through it in order, one task's
budget after the other; `sched.scheduler.run_campaign` interleaves many
tasks' rounds under one budget.

The policies around the loop are plugins:

  * adaptation scheme — a `Strategy` (autotune/strategies.py), resolved from
    a registered name or passed as an instance. The five paper strategies
    (paper §4.4: raw, ansor-random, tenset-pretrain, tenset-finetune, moses)
    ship registered; new schemes are one `@register_strategy` class.
  * scoring model — a `CostModel` (core/cost_model.py), resolved the same
    way ("mlp" is the paper default; "residual-mlp" ships as a second
    family). Strategies only ever see the interface.
  * measurement — a `MeasurementExecutor` (sched/executor.py): every trial
    goes through its `measure_fn`, with its timeouts, retries and
    quarantine. A failed measurement costs its seconds but leaves no
    record; the config lands on `TaskResult.poisoned`.

Search-time accounting mirrors the paper: on-device measurement dominates, so
search_time = sum(measurement_seconds) + small per-round model-update cost.
The AC module (moses only) truncates the measurement phase when the cost
model's CV stabilizes.

Hot path (see docs/architecture.md): each task owns a FeatureCache (every
distinct config featurized once) and a RecordsBuilder (records appended
incrementally, labels re-normalized per snapshot); all scoring goes through
`CostModel.batched_predict`, whose bucket padding keeps every call on one
compiled forward. Use `autotune.session.TuneSession` to run several (device,
strategy) jobs over shared pretrained params.

Determinism: the executor returns outcomes in submission order and the
simulator's noise keys on (config, trial), so results never depend on
thread timing. `repro.sched` imports this module, so the executor module is
imported where it is called.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.autotune import devices as dev_mod
from repro.autotune.evolution import evolutionary_search
from repro.autotune.space import (ProgramConfig, Workload,
                                  clip_config_to_space, default_config,
                                  workload_descriptor)
from repro.autotune.strategies import (Strategy, StrategyContext,
                                       resolve_strategy, strategy_name)
from repro.configs.moses import MosesConfig
from repro.core.cost_model import (CostModel, Records, RecordsBuilder,
                                   resolve_cost_model)
from repro.core.features import FeatureCache
from repro.obs import trace as obs_trace

if TYPE_CHECKING:
    from repro.obs.calibration import CalibrationTracker
    from repro.sched.executor import MeasurementExecutor
    from repro.sched.speculative import SpeculativeScorer

CONFIRM_TRIAL = 97      # trial index of the prediction-only confirmation


@dataclasses.dataclass
class TaskResult:
    workload: Workload
    best_config: ProgramConfig
    best_throughput: float          # GFLOP/s (noiseless eval)
    best_latency: float             # seconds per call (noiseless)
    measurements: int
    search_seconds: float
    trajectory: List[float]         # best-so-far throughput per measurement
    # every (config, measured throughput, trial index) triple, in
    # measurement order — what the transfer hub's record store persists
    # (trial matters: the simulator's noise redraws per trial, so the store
    # dedups on (task, config, trial)). None for legacy callers.
    measured: Optional[List[Tuple[ProgramConfig, float, int]]] = None
    # configs whose measurement failed under the executor (crash, timeout,
    # quarantine): (config, trial, error). The hub writes these to the store
    # as error records so a refreshed model knows which configs are hostile.
    # None for legacy callers.
    poisoned: Optional[List[Tuple[ProgramConfig, int, str]]] = None


@dataclasses.dataclass
class TuneResult:
    strategy: str
    device: str
    tasks: List[TaskResult]
    total_search_seconds: float
    # the adapted cost-model params at the end of the run (None for
    # model-free strategies). The transfer-provenance layer compares these
    # against the source ticket's params (lottery-mask overlap); they are
    # NOT persisted with the result itself.
    final_params: Optional[object] = None

    @property
    def model_latency(self) -> float:
        """End-to-end latency: sum over subgraphs of best latency x count."""
        return sum(t.best_latency * t.workload.count for t in self.tasks)

    @property
    def total_measurements(self) -> int:
        return sum(t.measurements for t in self.tasks)


def _noiseless_latency(wl: Workload, cfg: ProgramConfig, device: str) -> float:
    return dev_mod.execution_time(wl, cfg, dev_mod.DEVICES[device],
                                  noisy=False)


@dataclasses.dataclass
class RoundStats:
    """What one `step()` reports back to the scheduler."""
    measured: int               # records produced (excludes failures)
    failed: int                 # measurements that errored / timed out
    measure_seconds: float      # simulated on-device cost of the round
    update_seconds: float       # model-update cost the strategy reported
    wall_seconds: float         # parallel makespan estimate for the round
    # absolute best-latency improvement this round, weighted by the
    # workload's occurrence count — i.e. seconds shaved off the parent
    # model's latency, the quantity the campaign objective sums
    improvement: float
    terminated: bool            # strategy (AC) says stop measuring
    exhausted: bool             # config space ran dry

    @property
    def device_seconds(self) -> float:
        """Total simulated cost of the grant (the scheduler's budget)."""
        return self.measure_seconds + self.update_seconds


class TaskTuner:
    """One (device, workload) tuning job, advanced one round at a time.

    `rng` drives the search; `tune()` shares one across its task list, a
    campaign derives one per task. `warm_start` configs seed the first
    round's search only (cross-task transfer). Optional, for campaigns:
    `scorer` screens candidates draft-then-verify before the full cost
    model, and tasks on one device may share the Strategy instance and a
    `shared_builder`, under their own `group` id, so the shared model trains
    on the device's whole measurement corpus.
    """

    def __init__(self, wl: Workload, device: str, strategy: Strategy,
                 moses_cfg: MosesConfig, cost_model: CostModel,
                 rng: np.random.RandomState,
                 executor: MeasurementExecutor,
                 scorer: Optional[SpeculativeScorer] = None,
                 shared_builder: Optional[RecordsBuilder] = None,
                 group: int = 0,
                 calibration: Optional[CalibrationTracker] = None,
                 warm_start: Sequence[ProgramConfig] = ()):
        self.wl = wl
        self.device = device
        self.strategy = strategy
        self.cfg = moses_cfg
        self.cost_model = cost_model
        self.rng = rng
        self.executor = executor
        self.scorer = scorer
        # pure observer: records predicted-vs-measured calibration per
        # round; never touches the RNG or strategy state, so enabling it
        # changes no tuning result
        self.calibration = calibration
        self.shared_builder = shared_builder
        self.group = group
        self.warm_start = list(warm_start)
        strategy.begin_task(wl)
        # per-task strategy state (moses' AC state): with a shared strategy,
        # each tuner keeps its own snapshot and swaps it in around on_round,
        # so one task's §3.5 early-termination can never cascade to its
        # neighbors on the device
        self._task_state = strategy.task_state()

        self.seen: set = set()
        # (config, measured throughput, trial), in measurement order
        self.recorded: List[Tuple[ProgramConfig, float, int]] = []
        # (config, trial, error) of every failed measurement
        self.poisoned: List[Tuple[ProgramConfig, int, str]] = []
        self.traj: List[float] = []
        self.cache = FeatureCache()
        self.builder = RecordsBuilder()
        self.best_thr = float("-inf")
        self.best_cfg: Optional[ProgramConfig] = None
        self.best_latency = _noiseless_latency(wl, default_config(wl), device)
        self.search_seconds = 0.0
        self.meas_seconds = 0.0     # on-device measurement seconds only
        self.rounds = 0
        self.terminated = False
        self.exhausted = False
        self.finished = False

    @property
    def key(self) -> str:
        return f"{self.device}|{self.wl.key()}"

    @property
    def active(self) -> bool:
        """Whether a step may run: never for a model-free strategy (raw),
        whose `finish()` returns the vendor default."""
        return self.strategy.uses_model and not (
            self.terminated or self.exhausted or self.finished)

    # --- scoring ----------------------------------------------------------
    def _score_fn(self, feats: np.ndarray) -> np.ndarray:
        params = self.strategy.params
        if params is None:
            return self.rng.rand(len(feats))
        if self.scorer is not None:
            return self.scorer(params, feats)
        return self.cost_model.batched_predict(params, feats)

    def _search(self, top_k: int, **kw) -> List[ProgramConfig]:
        return evolutionary_search(
            self.wl, self._score_fn, self.rng,
            population=self.cfg.population_size,
            rounds=self.cfg.evolution_rounds, top_k=top_k, seen=self.seen,
            feature_cache=self.cache, **kw)

    def _refresh_best(self) -> None:
        cfg, _, _ = max(self.recorded, key=lambda t: t[1])
        if cfg is not self.best_cfg:
            self.best_cfg = cfg
            self.best_latency = _noiseless_latency(self.wl, cfg, self.device)

    def _record(self, cfg: ProgramConfig, thr: float, trial: int) -> None:
        self.recorded.append((cfg, thr, trial))
        self.best_thr = max(self.best_thr, thr)
        self.traj.append(self.best_thr)

    # --- one measured round -----------------------------------------------
    def step(self, batch_size: Optional[int] = None) -> RoundStats:
        from repro.sched.executor import batch_wall_seconds

        assert self.active, "step() on an inactive task"
        bsz = batch_size if batch_size is not None else self.cfg.top_k_measure
        prev_latency = self.best_latency
        # the params that score THIS round's search; on_round replaces them
        # below, so calibration must predict with the pre-update snapshot
        params_for_round = self.strategy.params
        with obs_trace.span("round.search", device=self.device,
                            task=self.wl.key()):
            cands = self._search(
                bsz, mutation_prob=self.cfg.mutation_prob,
                eps_greedy=self.cfg.eps_greedy,
                seed_configs=(self.warm_start if self.rounds == 0 else [])
                + [c for c, _, _ in
                   sorted(self.recorded, key=lambda t: -t[1])[:8]])
        if not cands:
            self.exhausted = True
            return RoundStats(0, 0, 0.0, 0.0, 0.0, 0.0, False, True)

        with obs_trace.span("round.measure", device=self.device,
                            task=self.wl.key(), n=len(cands)):
            feats = self.cache.features_batch(self.wl, cands)
            outcomes = self.executor.measure_batch(self.wl, cands,
                                                   self.device,
                                                   trial=self.rounds)
        ok_feats = []
        ok_thrs: List[float] = []
        for out, f in zip(outcomes, feats):
            if not out.ok:            # paid for, but poisoned: no record
                self.poisoned.append((out.request.config, out.request.trial,
                                      out.error or "failed"))
                continue
            thr = out.throughput
            self._record(out.request.config, thr, out.request.trial)
            self.builder.append(f, thr)
            if self.shared_builder is not None:
                self.shared_builder.append(f, thr, group=self.group)
            ok_feats.append(f)
            ok_thrs.append(thr)
        if (self.calibration is not None and ok_feats
                and params_for_round is not None):
            # cold-start rounds (random scores, no params) carry no model
            # signal; batched_predict is pure, so this observes without
            # perturbing the search
            preds = self.cost_model.batched_predict(params_for_round,
                                                    np.stack(ok_feats))
            self.calibration.observe_round(self.device, self.wl.key(),
                                           self.rounds, preds, ok_thrs)
        costs = [out.seconds for out in outcomes]
        measure_seconds = sum(costs)
        wall = batch_wall_seconds(costs, self.executor.workers)
        # apart from the update cost below, in the frozen legacy loop's
        # order, so tune()'s seconds match it bit for bit for any values
        self.search_seconds += measure_seconds

        terminated = False
        update_seconds = 0.0
        if ok_feats:
            self._refresh_best()
            train_builder = (self.shared_builder
                             if self.shared_builder is not None
                             else self.builder)
            # strategy hook: online model update on the incremental record
            # set (features were extracted once at measurement time; only
            # labels re-normalize) — each strategy snapshots only if it
            # trains, and reports its model-update cost + AC termination
            with obs_trace.span("round.update", device=self.device,
                                task=self.wl.key()):
                if self.shared_builder is not None:
                    self.strategy.set_task_state(self._task_state)
                upd = self.strategy.on_round(train_builder,
                                             np.stack(ok_feats), self.rounds)
                if self.shared_builder is not None:
                    self._task_state = self.strategy.task_state()
                if self.scorer is not None and not self.scorer.distill:
                    # label-supervised drafts must train on the same corpus
                    # the full model does — a task-local draft screening a
                    # device-corpus model discards candidates the stronger
                    # verifier would keep. (Distilling drafts feed
                    # themselves from every full-model evaluation; no
                    # snapshot needed.)
                    self.scorer.refit(train_builder.snapshot())
            update_seconds = upd.cost_seconds
            wall += upd.cost_seconds
            terminated = upd.terminate
            self.search_seconds += update_seconds
        self.meas_seconds += measure_seconds
        self.rounds += 1
        self.terminated = terminated
        improvement = (prev_latency - self.best_latency) * self.wl.count
        return RoundStats(len(ok_feats), len(outcomes) - len(ok_feats),
                          measure_seconds, update_seconds, wall, improvement,
                          terminated, False)

    # --- wrap-up ----------------------------------------------------------
    def finish(self, pred_trials: Optional[int] = None) -> TaskResult:
        """Prediction-only phase (explore with the adapted model, confirm its
        argmax with ONE measurement) + TaskResult assembly."""
        assert not self.finished
        self.finished = True
        n_pred = (pred_trials if pred_trials is not None
                  else self.cfg.top_k_measure)
        if n_pred > 0 and self.strategy.params is not None and self.recorded:
            with obs_trace.span("tune.finish", device=self.device,
                                task=self.wl.key()):
                cands = self._search(n_pred) or [default_config(self.wl)]
                scores = self.cost_model.batched_predict(
                    self.strategy.params,
                    self.cache.features_batch(self.wl, cands))
                top = cands[int(np.argmax(scores))]
                outcome = self.executor.measure_batch(
                    self.wl, [top], self.device, trial=CONFIRM_TRIAL)[0]
            if outcome.ok:
                self._record(top, outcome.throughput, CONFIRM_TRIAL)
            else:
                self.poisoned.append((top, CONFIRM_TRIAL,
                                      outcome.error or "failed"))
            self.search_seconds += outcome.seconds
            self.meas_seconds += outcome.seconds
        if not self.recorded:       # raw, or nothing survived: vendor default
            cfg = default_config(self.wl)
            lat = _noiseless_latency(self.wl, cfg, self.device)
            return TaskResult(self.wl, cfg, self.wl.flops / lat / 1e9, lat,
                              0, self.search_seconds, [], measured=[],
                              poisoned=self.poisoned)
        self._refresh_best()
        lat = self.best_latency
        return TaskResult(self.wl, self.best_cfg, self.wl.flops / lat / 1e9,
                          lat, len(self.recorded), self.search_seconds,
                          self.traj, measured=self.recorded,
                          poisoned=self.poisoned)


def tune(
    tasks: Sequence[Workload],
    device: str,
    strategy: Union[str, Strategy],
    moses_cfg: MosesConfig,
    trials_per_task: int = 200,
    pretrained_params=None,
    source_pool: Optional[Records] = None,
    seed: int = 0,
    ratio_override: Optional[float] = None,
    model_update_cost: float = 2.0,
    cross_task: bool = False,
    cost_model: Union[str, CostModel, None] = None,
    calibration=None,
) -> TuneResult:
    """Tune `tasks` on `device` under an adaptation `strategy`, one task's
    budget after the other.

    `strategy` and `cost_model` accept registered names (back-compat: the
    five paper strategies and "mlp" resolve exactly as the old string API
    did) or instances for anything custom. One strategy instance and one
    search RNG serve the whole task list, so the online model keeps learning
    from task to task.

    `calibration` (an `obs.CalibrationTracker`, optional) observes each
    measured batch's predicted-vs-measured calibration. Pure observer:
    passing one changes no tuning result.
    """
    from repro.sched.executor import resolve_executor

    strat = resolve_strategy(strategy)
    cm = resolve_cost_model(cost_model, moses_cfg.cost_model)
    strat.prepare(StrategyContext(
        cfg=moses_cfg, cost_model=cm, device=device, seed=seed,
        pretrained_params=pretrained_params, source_pool=source_pool,
        ratio_override=ratio_override, model_update_cost=model_update_cost))
    rng = np.random.RandomState(seed)
    results: List[TaskResult] = []
    # cross-task transfer archive (paper's stated future work; see
    # benchmarks/crosstask.py): (descriptor, best configs) of finished tasks
    archive: List = []
    executor, _ = resolve_executor(None)     # the campaigns' default pool
    with executor:
        for wl in tasks:
            tt = TaskTuner(wl, device, strat, moses_cfg, cm, rng, executor,
                           calibration=calibration,
                           warm_start=_warm_seeds(wl, archive))
            sizes, n_pred = strat.plan(trials_per_task)
            for i, bsz in enumerate(sizes):
                if not tt.active:
                    break
                if tt.step(bsz).terminated:
                    # AC early termination: the rest of the budget is pure
                    # cost-model prediction (paper §3.5)
                    n_pred += sum(sizes[i + 1:])
            results.append(_float32_rounds(tt.finish(pred_trials=n_pred)))
            if cross_task:
                top4 = sorted(results[-1].measured, key=lambda t: -t[1])[:4]
                archive.append((workload_descriptor(wl),
                                [c for c, _, _ in top4]))
    return TuneResult(strategy_name(strat), device, results,
                      sum(r.search_seconds for r in results),
                      final_params=strat.params)


def _warm_seeds(wl: Workload, archive: List) -> List[ProgramConfig]:
    """Cross-task warm start: the nearest finished task's best configs,
    clipped into this task's space."""
    if not archive:
        return []
    desc = workload_descriptor(wl)
    _, best_cfgs = min(((float(np.linalg.norm(desc - d)), cfgs)
                        for d, cfgs in archive), key=lambda t: t[0])
    clipped = (clip_config_to_space(wl, c) for c in best_cfgs)
    return [c for c in clipped if c is not None]


def _float32_rounds(r: TaskResult) -> TaskResult:
    """tune() reports each measured round's throughputs at float32, as it
    always has (tests/_legacy_tuner.py pins it); the confirmation
    measurement and every campaign throughput stay at full precision. No
    search decision reads the difference — the records builder trains on
    float32 labels either way — except the order of two throughputs of one
    task that are equal at float32."""
    measured = [(c, t if trial == CONFIRM_TRIAL else float(np.float32(t)),
                 trial) for c, t, trial in r.measured]
    traj = list(itertools.accumulate((t for _, t, _ in measured), max))
    return dataclasses.replace(r, measured=measured, trajectory=traj)
