"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --steps 200 --batch 8 --seq 128 [--smoke] [--autotune tpu_v5e] \
        [--checkpoint-dir /tmp/ckpt] [--resume]

--smoke uses the reduced same-family config (CPU-runnable); full configs need
the production mesh. --autotune runs Moses cost-model adaptation for the
target device first and persists tuned kernel configs to the registry (the
paper's pipeline as a pre-training step of the launcher). --source picks the
transfer source: a device name, or 'auto' to route through the transfer hub
(fingerprint the target, warm-start from the nearest measured device in the
persistent store; see src/repro/hub/). --scheduler gradient replaces the
serial fixed-budget tuner with the scheduled campaign engine
(src/repro/sched/): marginal-gain budget allocation, async measurement,
draft-then-verify scoring. --dry-run runs the autotune path on a tiny budget
and exits before training (the CI scheduler smoke leg).
"""
from __future__ import annotations

import argparse
from contextlib import nullcontext

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.moses import DEFAULT as MOSES_CFG
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.obs import get_logger
from repro.runtime import enable_compile_cache
from repro.train.data import DataConfig, data_iterator
from repro.train.optimizer import AdamW, AdamWConfig, cosine_schedule
from repro.train.train_loop import LoopConfig, run_training

log = get_logger("train")


def maybe_autotune(device: str, cfg, source: str = None,
                   hub_root: str = "artifacts/hub",
                   scheduler: str = "serial", trials: int = 48,
                   dry_run: bool = False, obs: str = None):
    from repro.autotune.dataset import generate_records, training_task_pool
    from repro.autotune.registry import Registry
    from repro.autotune.tasks import arch_tasks
    from repro.autotune.tuner import tune
    from repro.core.cost_model import resolve_cost_model

    tasks = arch_tasks(cfg)
    moses_cfg = MOSES_CFG
    if dry_run:
        # CI fast path: exercise the full scheduler/executor/hub machinery
        # on a CPU-minutes budget — two tasks, tiny search, shallow updates
        import dataclasses
        moses_cfg = dataclasses.replace(
            MOSES_CFG, online_epochs=2, adaptation_epochs=2,
            population_size=32, evolution_rounds=2, top_k_measure=8)
        tasks = tasks[:2]
        trials = min(trials, 16)
    if source == "auto":
        # route through the transfer hub: fingerprint the target, pick the
        # nearest measured source(s) from the persistent store (bootstrapping
        # the stock source corpus on first run), tune on miss, and persist
        # winners into the kernels' default registry
        from repro.hub import TuningHub, bootstrap_store
        log.info("Moses adaptation via hub", target=device,
                 hub_root=hub_root, scheduler=scheduler)
        hub = TuningHub(hub_root, moses_cfg=moses_cfg, registry=Registry(),
                        trials_per_task=trials, scheduler=scheduler)
        bootstrap_store(hub.store, [moses_cfg.source_device],
                        training_task_pool(include_archs=False),
                        programs_per_task=8 if dry_run else 16)
        queued = sum(hub.request(device, wl) for wl in tasks)
        results = hub.flush(device)
        sel = hub.selection(device)
        if sel is not None:
            log.info("transfer sources selected",
                     sources=[(d, round(w, 3)) for d, w in sel.sources])
        n = sum(len(r.tasks) for r in results)
        log.info("hub autotune done", tuned_tasks=n,
                 registry=hub.registry.path,
                 already_served=len(tasks) - queued)
        return

    src_device = source or moses_cfg.source_device
    log.info("Moses adaptation", source=src_device, target=device,
             scheduler=scheduler)
    pool = training_task_pool(include_archs=False)
    src = generate_records(pool, src_device,
                           programs_per_task=8 if dry_run else 24, seed=0)
    model = resolve_cost_model("mlp", moses_cfg.cost_model)
    params = model.init(jax.random.PRNGKey(0))
    params, _ = model.train(params, src, epochs=2 if dry_run else 10)
    reg = Registry()
    if scheduler == "gradient":
        from repro.autotune.session import TuneSession
        session = TuneSession(moses_cfg=moses_cfg, pretrained_params=params,
                              source_pool=src, registry=reg,
                              trials_per_task=trials)
        campaign = session.run_many([(device, tasks)], strategy="moses",
                                    scheduler="gradient", speculative=True,
                                    return_campaign=True, obs=obs)
        result = campaign.results[0]
        log.info("campaign done",
                 measurements=campaign.total_measurements,
                 simulated_s=round(campaign.spent_seconds, 1),
                 wall_s=round(campaign.wall_seconds, 1),
                 grants=len(campaign.trace),
                 draft_acceptance=round(campaign.spec_stats.acceptance, 2),
                 full_model_reduction=round(
                     campaign.spec_stats.full_model_reduction, 1))
        if obs:
            log.info("campaign telemetry written", obs_dir=obs)
    else:
        result = tune(tasks, device, "moses", moses_cfg,
                      trials_per_task=trials, pretrained_params=params,
                      source_pool=src, cost_model=model)
        reg.ingest(result)
    reg.save()
    log.info("autotune done", tuned_tasks=len(result.tasks), registry=reg.path)


def make_optimizer(cfg, lr: float, steps: int) -> AdamW:
    return AdamW(AdamWConfig(
        lr=cosine_schedule(lr, max(steps // 20, 1), steps),
        weight_decay=0.01, moment_dtype=cfg.moment_dtype,
        master_fp32=(cfg.param_dtype == "bfloat16")))


def perf_hints(mesh, opt: str):
    """The sharding-hint context for `--opt` (act | act,epmoe | none)."""
    from repro.distributed.act_sharding import Hints, use_hints
    from repro.distributed.sharding import data_axes
    tokens = set((opt or "none").split(","))
    if not tokens & {"act", "epmoe"}:
        return nullcontext()
    return use_hints(Hints(
        mesh, data_axes(mesh), "model",
        zero3_gather=False,
        constrain_activations="act" in tokens,
        moe_impl="expert_parallel" if "epmoe" in tokens else None))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--autotune", default=None,
                    help="target device for Moses kernel tuning")
    ap.add_argument("--source", default=None,
                    help="source device for --autotune transfer, or 'auto' "
                         "to select the nearest measured device via the "
                         "transfer hub's fingerprint ranking")
    ap.add_argument("--hub-root", default="artifacts/hub",
                    help="transfer-hub root used by --source auto")
    ap.add_argument("--scheduler", default="serial",
                    choices=("serial", "gradient"),
                    help="--autotune engine: 'serial' tunes each task with "
                         "a fixed budget; 'gradient' runs one scheduled "
                         "campaign (marginal-gain budget allocation + async "
                         "measurement + draft-then-verify scoring)")
    ap.add_argument("--autotune-trials", type=int, default=48,
                    help="per-task trial budget for --autotune")
    ap.add_argument("--dry-run", action="store_true",
                    help="run the --autotune path on a tiny budget and exit "
                         "before training (the CI scheduler smoke leg)")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="write campaign telemetry (events.jsonl + Chrome "
                         "trace + metrics snapshot) to DIR; applies to the "
                         "--scheduler gradient autotune path. Inspect with "
                         "`python -m repro.launch.obs --summarize DIR`")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--opt", default="act",
                    help="perf hints: act | act,epmoe | none "
                         "(EXPERIMENTS.md §Perf; act = pin scan-carry/block "
                         "activation shardings, epmoe = shard_map expert "
                         "parallelism)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dry_run and not args.autotune:
        ap.error("--dry-run needs --autotune DEVICE")
    if args.autotune:
        maybe_autotune(args.autotune, cfg, source=args.source,
                       hub_root=args.hub_root, scheduler=args.scheduler,
                       trials=args.autotune_trials, dry_run=args.dry_run,
                       obs=args.obs)
        if args.dry_run:
            log.info("dry-run: autotune path OK; skipping training")
            return

    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else
            make_host_mesh(model_parallel=args.model_parallel))
    model = build_model(cfg)
    opt = make_optimizer(cfg, args.lr, args.steps)
    data = data_iterator(cfg, DataConfig(batch_size=args.batch,
                                         seq_len=args.seq, seed=args.seed))
    loop = LoopConfig(total_steps=args.steps,
                      checkpoint_every=args.checkpoint_every,
                      checkpoint_dir=args.checkpoint_dir)
    with perf_hints(mesh, args.opt):
        state, hist = run_training(model, opt, mesh, data, loop,
                                   rng=jax.random.PRNGKey(args.seed))
    print(f"final loss: {hist[-1]['loss']:.4f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
