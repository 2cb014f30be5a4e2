"""Tuning Scheduler: multi-task budget allocation + async measurement.

Three cooperating pieces (see docs/architecture.md, "Tuning Scheduler"):

  * `scheduler.run_campaign` — gradient-based allocation of measurement
    rounds across (device, workload) jobs under a global budget;
  * `executor.MeasurementExecutor` — bounded measurement service with
    timeouts, retries, fault isolation, crash quarantine, and deterministic
    result ordering, selectable as ``backend="thread"`` (in-process pool)
    or ``backend="process"`` (spawn-context farm, `farm.py` — survives
    worker crashes and hard-kills wedged measurements);
  * `speculative.SpeculativeScorer` — Pruner-style draft-then-verify
    candidate screening in front of the full cost model.

`TuneSession.run_many(..., scheduler="gradient")` and
`TuningHub(scheduler="gradient")` are the integration points.
"""
from repro.sched.executor import (MeasureOutcome, MeasureRequest,
                                  MeasurementExecutor, QuarantinedConfig,
                                  ThreadMeasurementExecutor,
                                  batch_wall_seconds, resolve_executor)
from repro.sched.farm import ProcessMeasurementExecutor
from repro.sched.scheduler import (CampaignResult, SchedulerConfig,
                                   TraceEntry, run_campaign)
from repro.sched.speculative import (RandomFeatureDraft, RidgeDraft,
                                     SpecStats, SpeculativeScorer)

__all__ = [
    "CampaignResult", "MeasureOutcome", "MeasureRequest",
    "MeasurementExecutor", "ProcessMeasurementExecutor", "QuarantinedConfig",
    "RandomFeatureDraft", "RidgeDraft", "SchedulerConfig",
    "SpecStats", "SpeculativeScorer",
    "ThreadMeasurementExecutor", "TraceEntry", "batch_wall_seconds",
    "resolve_executor", "run_campaign",
]
