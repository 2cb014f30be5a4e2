"""Driver `serve`: closed-loop waves through `serve.Engine.generate`.

Set-up: the configuration's sizes become the program's model config; the
weights are made from the seed in one jitted call, in the program's own
layout and parameter type; one short wave per prompt width the mix will use
warms up the prefill and decode programs and the sampler.

Window: whole waves of the mix (see `mixes.py`), each started while less
than `seconds` have passed; it ends when the last wave's tokens are on the
host. The bench stamps its own clock each time the engine's sampler hands
a wave's next tokens to the host: the gaps between a wave's stamps are the
times between output tokens. The engine's `serve.engine.*` instruments,
which the per-layer readers use, are read from a metrics registry pushed
for the window.

Check: after the window, with the program's state freed, a sample of the
finished requests drawn from the seed, with the longest answer in it. The
plain reference (`reference.decoder_logits`, float32) runs over each
request's prompt row as the engine fed it (left-padded with id 0 to the
wave's longest prompt; the engine attends to the padding) followed by its
served tokens, and the widest gap by which a served token's logit lies
below the reference's best is compared with its limit.
"""
from __future__ import annotations

import time

import numpy as np

import counts
import harness
import mixes
import reference as ref

def program_config(s: dict, program: dict):
    """The program's config for the configuration file's sizes."""
    from repro.configs import get_config
    return get_config(program["arch"]).replace(
        num_layers=s["layers"], d_model=s["d_model"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"], d_ff=s["d_ff"],
        vocab_size=s["vocab"], sliding_window=s["window"],
        rope_theta=s["rope_theta"], use_glu=s["glu"],
        param_dtype=s["param_dtype"], activation_dtype=s["activation_dtype"])


def program_params(w: dict) -> dict:
    """The bench's weights in the program's parameter tree (one scanned
    group of all layers)."""
    block = {"ln_attn": {"scale": w["ln_attn"]},
             "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "ln_mlp": {"scale": w["ln_mlp"]},
             "mlp": {"wi": w["wi"], "wo": w["wo_mlp"],
                     **({"wg": w["wg"]} if "wg" in w else {})}}
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]},
            "stack": {"prefix": {}, "groups": {"b0": block}, "suffix": {}}}


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.obs import metrics as obs_metrics
    from repro.serve import Engine, Request

    s, t = ctx.config["sizes"], ctx.traffic
    cfg = program_config(s, ctx.config["program"])
    model = build_model(cfg)
    key = harness.jax_seed(ctx.seed)
    want = jax.eval_shape(model.init, key)
    make = jax.jit(lambda k: program_params(ref.make_weights(k, s)))
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise harness.BenchError("the bench's weights do not match the "
                                 "program's parameter tree")
    params = make(key)
    engine = Engine(model, params, make_host_mesh(), max_len=t["max_len"],
                    batch_slots=t["wave"], seed=ctx.seed)
    for width in mixes.padded_lengths(t, len(t["prompt_lens"])):
        warm = [Request(prompt=np.ones(width, np.int32), max_new_tokens=2)
                for _ in range(t["wave"])]
        engine.generate(warm)
    setup_s = time.perf_counter() - ctx.t0

    stamps = []     # per wave: host clock when each token step was sampled
    sample = engine._sample

    def stamped(logits, temps):
        out = sample(logits, temps)
        stamps[-1].append(time.perf_counter())
        return out

    engine._sample = stamped
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.push_registry(reg)
    seconds, trace_dir = harness.start_window(ctx)
    waves = []
    try:
        with ctx.spans("bench.window"):
            w0 = time.perf_counter()
            while not waves or time.perf_counter() - w0 < seconds:
                wave = [Request(prompt=p, max_new_tokens=m)
                        for p, m in mixes.serve_wave(t, s["vocab"], ctx.seed,
                                                     len(waves))]
                stamps.append([])
                with ctx.spans("bench.wave"):
                    engine.generate(wave)
                waves.append(wave)
            window_s = time.perf_counter() - w0
    finally:
        obs_metrics.pop_registry(reg)
        harness.stop_window(ctx)
    peak = harness.memory_peak_bytes()
    token_gaps = np.concatenate([np.diff(w) for w in stamps])
    prefill_hist = reg.histogram("serve.engine.prefill_seconds")
    step_hist = reg.histogram("serve.engine.step_seconds")

    requests = [r for wave in waves for r in wave]
    failed = sum(1 for r in requests
                 if len(r.out_tokens) != r.max_new_tokens or not all(
                     0 <= x < s["vocab"] for x in r.out_tokens))
    prompt_tokens = sum(len(r.prompt) for r in requests)
    generated = sum(len(r.out_tokens) for r in requests)

    # work the window needed: real prompt tokens, and each decode step's
    # rows still producing tokens with their context lengths
    prefill_flops = sum(counts.prefill_flops(s, len(r.prompt))
                        for r in requests)
    pbytes = np.dtype(s["param_dtype"]).itemsize
    least = 0.0
    for wave in waves:
        width = max(len(r.prompt) for r in wave)
        for n in range(1, max(r.max_new_tokens for r in wave)):
            ctxs = [width + n - 1 for r in wave if r.max_new_tokens > n]
            least += counts.least_seconds(
                *counts.decode_step(s, ctxs, pbytes), ctx.device["peaks"])

    # the check, with the program's state freed first
    rng = np.random.default_rng(ctx.seed)
    done = [i for i, r in enumerate(requests) if r.out_tokens]
    longest = max(done, key=lambda i: len(requests[i].out_tokens))
    rest = [i for i in done if i != longest]
    pick = [longest] + list(rng.choice(
        rest, size=min(len(rest), t["check_requests"] - 1), replace=False))
    rows = []
    for i in pick:
        wave = waves[i // t["wave"]]
        width = max(len(r.prompt) for r in wave)
        r = requests[i]
        rows.append(([0] * (width - len(r.prompt)) + [int(x) for x in
                                                     r.prompt],
                     list(r.out_tokens)))
    ref.free(params, engine.params)
    del engine, params
    w = jax.jit(lambda k: ref.make_weights(k, s))(key)
    gap = max(float(ref.served_gaps(w, s, row, served).max())
              for row, served in rows)
    ref.free(w)
    checks = [harness.Check("served_logit_gap", gap,
                            ctx.limits["served_logit_gap"]),
              harness.Check("unfinished_requests", failed, 0)]

    return harness.Outcome(
        setup_s=setup_s, attempted=len(requests),
        failed=failed,
        e2e={"prefill_tok_s": prompt_tokens / window_s,
             "decode_tok_s": generated / window_s,
             "tpot_p95_ms": float(np.percentile(
                 token_gaps, 95, method="inverted_cdf")) * 1e3},
        checks=checks,
        counts={"prefill_flops": prefill_flops,
                "prefill_seconds": prefill_hist.total,
                "decode_least_s": least, "step_seconds": step_hist.total,
                "steps": step_hist.count, "waves": len(waves),
                "checked_tokens": sum(len(sv) for _, sv in rows)},
        trace_dir=trace_dir, memory_peak_bytes=peak)
