"""Train/serve step builders with pjit shardings + the fault-tolerant loop.

make_train_step(model, opt, mesh)  -> jitted (train_state, batch) -> (state, metrics)
make_serve_prefill / make_serve_step -> jitted serving entry points

TrainState = {"params", "opt": AdamW state, "step": int32}

The training loop (run_training) adds: checkpoint/restart, straggler watchdog
(step-time anomaly detection), and preemption simulation hooks used by tests.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models.model import Model
from repro.train.optimizer import AdamW
from repro.train.checkpoint import CheckpointManager

PyTree = Any


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def train_state_shardings(model: Model, opt: AdamW, mesh: Mesh):
    """Shardings for {"params","opt","step"} without allocating anything."""
    cfg = model.cfg
    params_shape, axes = model.abstract_params_and_axes()
    p_shard = sh.param_shardings(params_shape, axes, mesh, cfg.sharding_plan)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    replicated = NamedSharding(mesh, P())

    def opt_shards(opt_shape_tree):
        out = {}
        for k, v in opt_shape_tree.items():
            if k == "count":
                out[k] = replicated
            else:
                out[k] = p_shard  # m/v/master inherit the param sharding
        return out

    return {"params": p_shard, "opt": opt_shards(opt_shape),
            "step": replicated}, params_shape, opt_shape


def make_train_step(model: Model, opt: AdamW, mesh: Mesh,
                    microbatches: int = 1, donate: bool = True):
    cfg = model.cfg

    def step_fn(train_state, batch):
        params = train_state["params"]

        def loss_fn(p, b):
            return model.loss(p, b)

        if microbatches > 1:
            # gradient accumulation over the batch split along dim 0
            def micro(b, i):
                return jax.tree.map(
                    lambda x: x.reshape(microbatches, -1, *x.shape[1:])[i], b)

            def body(carry, i):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, micro(batch, i))
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)),
                jnp.arange(microbatches))
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics: Dict[str, Any] = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, opt_metrics = opt.update(
            grads, train_state["opt"], params)
        out = {"params": new_params, "opt": new_opt,
               "step": train_state["step"] + 1}
        m = {"loss": loss, **metrics, **opt_metrics}
        return out, m

    state_shardings, _, _ = train_state_shardings(model, opt, mesh)
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def init_train_state(model: Model, opt: AdamW, mesh: Mesh, rng) -> PyTree:
    state_shardings, _, _ = train_state_shardings(model, opt, mesh)

    def build(rng):
        params = model.init(rng)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    return jax.jit(build, out_shardings=state_shardings)(rng)


# ---------------------------------------------------------------------------
# Fault-tolerant training loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_n: int = 3
    async_checkpoint: bool = True
    log_every: int = 10
    straggler_factor: float = 3.0   # step slower than factor*median -> warn
    straggler_window: int = 20


def run_training(model: Model, opt: AdamW, mesh: Mesh,
                 data_iter: Iterator[Dict[str, np.ndarray]],
                 loop: LoopConfig,
                 rng=None,
                 train_state: Optional[PyTree] = None,
                 fail_at_step: Optional[int] = None,
                 log_fn: Callable[[str], None] = print):
    """Runs training with checkpoint/restart. Returns (train_state, history).

    fail_at_step simulates a node failure (raises) — tests restart from the
    latest checkpoint and verify continuation.
    """
    ckpt = CheckpointManager(loop.checkpoint_dir, keep_n=loop.keep_n,
                             async_save=loop.async_checkpoint)
    step_fn = make_train_step(model, opt, mesh)
    if train_state is None:
        latest = ckpt.latest_step()
        if latest is not None:
            like = jax.eval_shape(
                lambda r: {"params": model.init(r), "opt": opt.init(model.init(r)),
                           "step": jnp.zeros((), jnp.int32)},
                jax.random.PRNGKey(0))
            shardings, _, _ = train_state_shardings(model, opt, mesh)
            train_state = ckpt.restore(latest, like, shardings)
            log_fn(f"[restart] restored step {latest} from {loop.checkpoint_dir}")
        else:
            train_state = init_train_state(
                model, opt, mesh, rng if rng is not None else jax.random.PRNGKey(0))

    from repro.obs import metrics as obs_metrics
    step_hist = obs_metrics.current().histogram("train.step_seconds")
    history = []
    times: list = []
    step = int(jax.device_get(train_state["step"]))
    while step < loop.total_steps:
        batch = next(data_iter)
        batch = jax.tree.map(jnp.asarray, batch)
        t0 = time.perf_counter()
        if fail_at_step is not None and step == fail_at_step:
            raise RuntimeError(f"simulated node failure at step {step}")
        train_state, metrics = step_fn(train_state, batch)
        metrics = jax.device_get(metrics)
        dt = time.perf_counter() - t0
        step_hist.observe(dt)
        times.append(dt)
        if len(times) > loop.straggler_window:
            times.pop(0)
            med = float(np.median(times))
            if dt > loop.straggler_factor * med:
                log_fn(f"[straggler] step {step} took {dt:.3f}s "
                       f"(median {med:.3f}s) — mitigation hook fired")
        step += 1
        history.append({"step": step, **{k: float(v) for k, v in metrics.items()}})
        if step % loop.log_every == 0:
            log_fn(f"step {step:6d} loss {history[-1]['loss']:.4f} "
                   f"gnorm {history[-1].get('grad_norm', 0):.3f} {dt*1e3:.0f}ms")
        if step % loop.checkpoint_every == 0 or step == loop.total_steps:
            ckpt.save(step, train_state)
    ckpt.wait()
    return train_state, history


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def make_serve_prefill(model: Model, mesh: Mesh, max_len: Optional[int] = None):
    def serve_prefill(params, batch):
        return model.prefill(params, batch, max_len=max_len)
    return jax.jit(serve_prefill)


def make_serve_step(model: Model, mesh: Mesh, distributed_cache: bool = False):
    extras = {}
    if distributed_cache:
        from repro.distributed.decode_attention import make_distributed_attend_fn
        extras["attend_fn"] = make_distributed_attend_fn(mesh)

    def serve_decode(params, state, tokens):
        st = dict(state)
        st["extras"] = {**state.get("extras", {}), **extras}
        return model.decode_step(params, st, tokens)

    return jax.jit(serve_decode)
