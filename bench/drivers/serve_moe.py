"""Driver `serve_moe`: closed-loop waves of a DeepSeek-V3 chip share through
`serve.Engine.generate`.

As the `serve` driver, for a configuration of latent attention and routed
experts (`reference_mla_moe.sizes`):

Set-up: the configuration's keys become the program's `deepseek-v3-671b`
config with the chip's share (its layers, its held experts, its slice of
the vocabulary); the weights are made from the seed in one jitted call in
the program's parameter tree and dtype; the engine prefills each wave in
groups of `prefill_rows` rows; one wave per prompt width the mix will use
warms up the prefill and decode programs and the sampler. In the traced
run the compiled decode program's text is read once, in set-up, for the
named scope of each of its ops (`hloscope.op_scopes`), which the
`serve.decode.*_share` readers use.

Window: whole waves of the mix (`mixes.serve_wave`), each started while
less than `seconds` have passed. The bench stamps its own clock each time
the engine's sampler hands a wave's next tokens to the host.

Check: after the window, with the program's state freed, a sample of the
finished requests drawn from the seed, with the longest answer in it. The
plain reference (`reference_mla_moe.decoder_logits`, float32, the weights
upcast a layer at a time) runs over each request's prompt row as the
engine fed it (left-padded with id 0 to the wave's longest prompt) followed
by its served tokens. The gap by which each served token's logit lies below
the reference's best is averaged over every checked token and compared with
its limit. The mean, and not the widest gap of the `serve` cells: the
router's choice is discontinuous, so a near-tie that rounding to bfloat16
flips moves a held expert in or out and a few tokens' logits by tenths,
which puts the widest gap of a sound bfloat16 run where the float8
control's lies; the mean stays an order of magnitude apart.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

import counts
import counts_mla_moe
import harness
import hloscope
import mixes
import reference as ref
import reference_mla_moe as ref_mm

ARCH = "deepseek-v3-671b"


def program_config(s: dict):
    """The program's config for the sizes that run."""
    from repro.configs import MLAConfig, RopeScaling, get_config
    base = get_config(ARCH)
    return base.replace(
        num_layers=s["layers"], d_model=s["d_model"], num_heads=s["heads"],
        num_kv_heads=s["heads"], d_ff=s["d_ff"], vocab_size=s["vocab"],
        rope_theta=s["rope_theta"],
        rope_scaling=RopeScaling(**{k: v for k, v in s["rope_scaling"].items()
                                    if k != "type"}),
        mla=MLAConfig(q_lora_rank=s["q_lora"], kv_lora_rank=s["kv_lora"],
                      qk_nope_head_dim=s["qk_nope"],
                      qk_rope_head_dim=s["qk_rope"], v_head_dim=s["v_head"]),
        moe=dataclasses.replace(
            base.moe, num_experts=s["experts"], top_k=s["top_k"],
            d_ff_expert=s["d_ff_expert"], num_shared_experts=s["shared"],
            d_ff_shared=s["d_ff_expert"],
            first_dense_layers=s["dense_layers"], n_group=s["n_group"],
            topk_group=s["topk_group"],
            routed_scaling_factor=s["routed_scaling_factor"],
            experts_held=s["experts_held"], first_expert=s["first_expert"]),
        param_dtype=s["param_dtype"], activation_dtype=s["activation_dtype"])


_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo")


def program_params(w: dict, s: dict) -> dict:
    """The bench's weights in the program's parameter tree: the dense
    layers in the stack's prefix, the expert layers as one scanned group."""
    import jax

    def block(kind, ffn):
        return {"ln_attn": {"scale": w[kind + ".ln_attn"]},
                "attn": {n: w[f"{kind}.{n}"] for n in _ATTN},
                "ln_mlp": {"scale": w[kind + ".ln_mlp"]}, **ffn}

    dense = block("dense", {"mlp": {"wi": w["dense.wi"], "wg": w["dense.wg"],
                                    "wo": w["dense.wo_mlp"]}})
    moe = block("moe", {"moe": {
        "router": w["moe.router"], "router_bias": w["moe.router_bias"],
        "wi": w["moe.wi"], "wg": w["moe.wg"], "wo": w["moe.wo_mlp"],
        **{n: w["moe." + n] for n in ("shared_wi", "shared_wg",
                                      "shared_wo")}}})
    prefix = {f"l{i}": jax.tree.map(lambda a, i=i: a[i], dense)
              for i in range(s["dense_layers"])}
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]},
            "stack": {"prefix": prefix, "groups": {"b0": moe},
                      "suffix": {}}}


def _decode_scopes(engine, ctx) -> dict:
    """Wrap the engine's decode step so that its first call also reads the
    compiled program's text (a compile-cache hit where the cache is on) for
    the scope of each op; returns the dict it fills."""
    step, found = engine._step, {}

    def first_call(*args):
        engine._step = step
        text = step.lower(*args).compile().as_text()
        found.update(hloscope.op_scopes(text))
        ctx.log(f"[bench] decode program: {len(found)} ops with a scope")
        return step(*args)

    engine._step = first_call
    return found


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.obs import metrics as obs_metrics
    from repro.serve import Engine, Request

    s, t = ref_mm.sizes(ctx.config), ctx.traffic
    model = build_model(program_config(s))
    key = harness.jax_seed(ctx.seed)
    want = jax.eval_shape(model.init, key)
    make = jax.jit(lambda k: program_params(ref_mm.make_weights(k, s), s))
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise harness.BenchError("the bench's weights do not match the "
                                 "program's parameter tree")
    params = make(key)
    engine = Engine(model, params, make_host_mesh(), max_len=t["max_len"],
                    batch_slots=t["wave"], seed=ctx.seed,
                    prefill_rows=t["prefill_rows"])
    scopes = _decode_scopes(engine, ctx) if ctx.trace else {}
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
    step_hist = reg.histogram("serve.engine.step_seconds")

    requests = [r for wave in waves for r in wave]
    failed = sum(1 for r in requests
                 if len(r.out_tokens) != r.max_new_tokens or not all(
                     0 <= x < s["vocab"] for x in r.out_tokens))
    generated = sum(len(r.out_tokens) for r in requests)

    # each decode step's rows still producing tokens, with their contexts
    pbytes = np.dtype(s["param_dtype"]).itemsize
    least = 0.0
    for wave in waves:
        width = max(len(r.prompt) for r in wave)
        for n in range(1, max(r.max_new_tokens for r in wave)):
            ctxs = [width + n - 1 for r in wave if r.max_new_tokens > n]
            least += counts.least_seconds(
                *counts_mla_moe.decode_step(s, ctxs, pbytes),
                ctx.device["peaks"])

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
    w = jax.jit(lambda k: ref_mm.make_weights(k, s))(key)
    gaps = np.concatenate([ref_mm.served_gaps(w, s, row, served)
                           for row, served in rows])
    ref.free(w)
    print(f"[bench] checked {len(rows)} requests, {gaps.size} tokens: "
          f"mean gap {float(gaps.mean())!r}, widest {float(gaps.max())!r}",
          file=sys.stderr, flush=True)
    checks = [harness.Check("served_logit_gap_mean", float(gaps.mean()),
                            ctx.limits["served_logit_gap_mean"]),
              harness.Check("unfinished_requests", failed, 0)]

    return harness.Outcome(
        setup_s=setup_s, attempted=len(requests), failed=failed,
        e2e={"decode_tok_s": generated / window_s,
             "tpot_p95_ms": float(np.percentile(
                 token_gaps, 95, method="inverted_cdf")) * 1e3},
        checks=checks,
        counts={"decode_least_s": least, "step_seconds": step_hist.total,
                "steps": step_hist.count, "waves": len(waves),
                "checked_tokens": sum(len(sv) for _, sv in rows),
                "decode_op_scopes": scopes},
        trace_dir=trace_dir, memory_peak_bytes=peak)
