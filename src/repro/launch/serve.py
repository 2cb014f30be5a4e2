"""Serving launcher: batched generation with the Engine.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke \
        --requests 8 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.runtime import enable_compile_cache
from repro.serve import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    extra = {}
    rng = np.random.RandomState(args.seed)
    if cfg.is_encoder_decoder:
        extra["encoder_embeddings"] = rng.randn(
            args.batch_slots, cfg.encoder_seq_len,
            cfg.frontend_dim or cfg.d_model).astype(np.float32) * 0.1
    elif cfg.cross_attn_every > 0:
        extra["frontend_embeddings"] = rng.randn(
            args.batch_slots, cfg.num_frontend_tokens,
            cfg.frontend_dim or cfg.d_model).astype(np.float32) * 0.1

    engine = Engine(model, params, mesh,
                    max_len=args.prompt_len + args.max_new + 8,
                    batch_slots=args.batch_slots, extra_batch=extra,
                    seed=args.seed)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size,
                                       size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    t0 = time.time()
    engine.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s)")
    for i, r in enumerate(reqs[:4]):
        print(f"  req{i}: {r.out_tokens[:12]}...")


if __name__ == "__main__":
    main()
