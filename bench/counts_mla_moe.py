"""Exact operation and byte counts of a DeepSeek-V3 decode step on one
chip's share (`reference_mla_moe.sizes`): absorbed MLA against the latent
cache, the held experts, the shared expert and the dense layers.

Kept with the benchmark, not taken from the program, and counted from the
shapes alone. The routed experts' operations are those of the assignments
the held experts are expected to get under even routing (a row's top_k
picks land on the held experts experts_held / experts of the time); every
held expert's weights are read once, since a step of many rows sends each
of them some.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16, F32 = 2, 4
F32_PARTS = ("norms", "router_bias")     # kept in float32 by the program


def mla_weights(s: dict) -> int:
    """Parameters of one layer's MLA matrices."""
    d, H = s["d_model"], s["heads"]
    ql, kl, dn, dr, dv = (s["q_lora"], s["kv_lora"], s["qk_nope"],
                          s["qk_rope"], s["v_head"])
    return (d * ql + ql * H * (dn + dr) + d * (kl + dr) + kl * H * dn
            + kl * H * dv + H * dv * d)


def weights(s: dict) -> Dict[str, int]:
    """Parameters by part, over all layers."""
    d, L = s["d_model"], s["layers"]
    Ld, Lm = s["dense_layers"], s["layers"] - s["dense_layers"]
    fe = s["d_ff_expert"]
    return {
        "mla": L * mla_weights(s),
        "dense_mlp": Ld * 3 * d * s["d_ff"],
        "router": Lm * d * s["experts"],
        "router_bias": Lm * s["experts"],
        "experts": Lm * s["experts_held"] * 3 * d * fe,
        "shared": Lm * s["shared"] * 3 * d * fe,
        "norms": L * (2 * d + s["q_lora"] + s["kv_lora"]) + d,
        "embed": s["vocab"] * d,
        "lm_head": d * s["vocab"],
    }


def cache_bytes_per_position(s: dict) -> int:
    """One layer's latent cache entry: the compressed KV and the rope key."""
    return BF16 * (s["kv_lora"] + s["qk_rope"])


def decode_step(s: dict, contexts: Iterable[int], param_bytes: int
                ) -> Tuple[float, float]:
    """(flops, least bytes) of one decode step for the rows still producing
    tokens, each attending over `context` cached positions: every weight
    but the embedding read once (of the embedding, the batch's rows), each
    row's cached latents read and its new entry written in every layer."""
    ctx = list(contexts)
    B, d, H = len(ctx), s["d_model"], s["heads"]
    L, Lm = s["layers"], s["layers"] - s["dense_layers"]
    kl, dr, fe = s["kv_lora"], s["qk_rope"], s["d_ff_expert"]
    w = weights(s)
    held_share = s["top_k"] * s["experts_held"] / s["experts"]
    per_row = (2.0 * L * mla_weights(s)          # absorbed: wk_b, wv_b once
               + 2.0 * w["dense_mlp"]
               + 2.0 * Lm * d * s["experts"]
               + 2.0 * Lm * held_share * 3 * d * fe
               + 2.0 * w["shared"]
               + 2.0 * d * s["vocab"])
    # scores against [latent, rope key], then the latent context
    attn = 2.0 * L * H * (2 * kl + dr) * sum(ctx)
    flops = B * per_row + attn
    w["embed"] = B * d
    nbytes = (sum(n * (F32 if k in F32_PARTS else param_bytes)
                  for k, n in w.items())
              + L * cache_bytes_per_position(s) * (sum(ctx) + B))
    return flops, float(nbytes)
