"""Batched serving engine: prefill + decode with KV cache.

Continuous-batching-lite: a fixed pool of batch slots; finished sequences
(EOS or budget) free their slot and queued requests are admitted at the next
prefill boundary. Per-slot positions (`cur` is per-sequence) make mixed-age
batches correct.

The engine serves from `Model.serving_params(params)`, made once at
construction: the weights every step reads only as a cast to the compute
dtype are held in it, so no step casts them again. The caller keeps its
tree.

With `prefill_rows` set, a wave is prefilled in groups of at most that many
rows, one call each, and their caches and logits are joined along the batch
axis before the wave decodes as one batch: a prefill's activations grow with
its rows, a decode step's far less. By default the whole wave is one call.

Observability: at construction the engine sets the gauges
`serve.engine.cast_weight_bytes`, the bytes of the weights it holds cast
to the compute dtype (0 when the stored dtype is the compute dtype), and
`serve.engine.experts_held`, the routed experts per MoE layer whose weights
it holds (0 for a model without experts). Every
wave records prefill and per-step decode wall time into the active metrics
registry (`serve.engine.prefill_seconds`, `serve.engine.step_seconds`,
`serve.engine.tokens`), and opens
`obs.trace` spans at each phase, which land in a recording JAX profiler
trace on the device ops' clock with their counts as event stats:

- `serve.wave` (wave, rows, width): the whole wave;
- `serve.prefill` (wave, rows, width, real_tokens, padded_tokens, groups):
  the prefill calls (`groups` of them), the join and the first sample;
- `serve.step` (wave, step, active_rows): one decode step, from the token
  upload to the end of its bookkeeping, so steps tile the decode loop;
  inside it `serve.step.dispatch` (token upload and step dispatch),
  `serve.step.sample` (sampler and its device-to-host read) and
  `serve.step.bookkeep` (the per-slot loop).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span
from repro.train.train_loop import make_serve_prefill, make_serve_step


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # [S] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    temperature: float = 0.0     # 0 = greedy
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    def __init__(self, model: Model, params, mesh, max_len: int = 512,
                 batch_slots: int = 8, distributed_cache: bool = False,
                 extra_batch: Optional[Dict[str, Any]] = None, seed: int = 0,
                 prefill_rows: Optional[int] = None):
        self.model = model
        self.params = model.serving_params(params)
        reg = obs_metrics.current()
        reg.gauge("serve.engine.cast_weight_bytes").set(
            sum(a.nbytes for a, b in zip(jax.tree.leaves(self.params),
                                         jax.tree.leaves(params))
                if a.dtype != b.dtype))
        mo = model.cfg.moe
        reg.gauge("serve.engine.experts_held").set(
            0 if mo is None else mo.experts_held or mo.num_experts)
        self.prefill_rows = prefill_rows
        self.mesh = mesh
        self.max_len = max_len
        self.batch_slots = batch_slots
        self.extra_batch = extra_batch or {}
        self._waves = 0
        self._prefill = make_serve_prefill(model, mesh, max_len=max_len)
        self._step = make_serve_step(model, mesh,
                                     distributed_cache=distributed_cache)
        self._rng = jax.random.PRNGKey(seed)

    def _sample(self, logits: jax.Array, temps: np.ndarray) -> np.ndarray:
        self._rng, sub = jax.random.split(self._rng)
        greedy = jnp.argmax(logits, axis=-1)
        t = jnp.asarray(np.maximum(temps, 1e-6))[:, None]
        sampled = jax.random.categorical(sub, logits / t, axis=-1)
        pick = jnp.where(jnp.asarray(temps) > 0, sampled, greedy)
        return np.asarray(pick, np.int32)

    def generate(self, requests: Sequence[Request]) -> List[Request]:
        """Serves all requests (batched waves of up to batch_slots)."""
        queue = list(requests)
        while queue:
            wave = queue[: self.batch_slots]
            queue = queue[self.batch_slots:]
            self._run_wave(wave)
        return list(requests)

    def _run_wave(self, wave: List[Request]):
        reg = obs_metrics.current()
        prefill_hist = reg.histogram("serve.engine.prefill_seconds")
        step_hist = reg.histogram("serve.engine.step_seconds")
        tokens = reg.counter("serve.engine.tokens")
        w = self._waves
        self._waves += 1
        B = len(wave)
        S = max(len(r.prompt) for r in wave)
        with span("serve.wave", wave=w, rows=B, width=S):
            toks = np.zeros((B, S), np.int32)
            for i, r in enumerate(wave):  # left-pad to a common length
                toks[i, S - len(r.prompt):] = r.prompt
            batch = {"tokens": jnp.asarray(toks), **self.extra_batch}
            temps = np.array([r.temperature for r in wave], np.float32)
            real = sum(len(r.prompt) for r in wave)
            per_call = self.prefill_rows or B
            with span("serve.prefill", wave=w, rows=B, width=S,
                      real_tokens=real, padded_tokens=B * S - real,
                      groups=-(-B // per_call)):
                t0 = time.perf_counter()
                if per_call >= B:
                    state, logits = self._prefill(self.params, batch)
                else:
                    parts = [self._prefill(self.params, {
                        k: v[g:g + per_call] for k, v in batch.items()})
                        for g in range(0, B, per_call)]
                    state = self.model.join_states([s for s, _ in parts])
                    logits = jnp.concatenate([l for _, l in parts])
                    del parts      # free the groups' caches before decode
                next_tok = self._sample(logits, temps)
                prefill_hist.observe(time.perf_counter() - t0)
            active = np.ones(B, bool)
            budget = np.array([r.max_new_tokens for r in wave])
            for i, r in enumerate(wave):
                r.out_tokens.append(int(next_tok[i]))
            tokens.inc(B)
            n = 1
            while active.any() and n < budget.max():
                rows = int(active.sum())
                with span("serve.step", wave=w, step=n, active_rows=rows):
                    t0 = time.perf_counter()
                    with span("serve.step.dispatch"):
                        state, logits = self._step(self.params, state,
                                                   jnp.asarray(next_tok))
                    with span("serve.step.sample"):
                        next_tok = self._sample(logits, temps)
                    step_hist.observe(time.perf_counter() - t0)
                    tokens.inc(rows)
                    n += 1
                    with span("serve.step.bookkeep"):
                        for i, r in enumerate(wave):
                            if not active[i]:
                                continue
                            tok = int(next_tok[i])
                            if n <= r.max_new_tokens:
                                r.out_tokens.append(tok)
                            if (r.eos_id is not None and tok == r.eos_id) or \
                                    len(r.out_tokens) >= r.max_new_tokens:
                                active[i] = False
                                r.done = True
        for r in wave:
            r.done = True
