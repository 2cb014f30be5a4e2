"""Plain reference of a DeepSeek-V3 decoder (MLA + routed experts), and the
weights the benchmark makes for it.

Nothing here imports the program. It follows the published model
(arXiv:2412.19437; Hugging Face `modeling_deepseek.py` of
deepseek-ai/DeepSeek-V3) in float32 at `Precision.HIGHEST`, with no cache,
kernels or batching; with `low` every matmul operand is rounded to float8
e4m3 (`reference.q8`), the control the comparisons must reject. It runs at
the CPU tests' small sizes and on the chip at the cell's, where the weights
are held in bfloat16 and upcast one layer at a time.

Each layer is pre-norm: x + MLA(norm(x)), then x + FFN(norm(x)), where FFN
is a SiLU-gated MLP in the first `dense_layers` layers and the routed
experts plus the shared expert after them.

- MLA: q = wq_b(rmsnorm(wq_a x)), split per head into 128 nope and 64 rope
  dims; [c, k_rope] = wkv_a x, c = rmsnorm(c); per head k = [wk_b c,
  k_rope] and v = wv_b c (the non-absorbed form). RoPE with YaRN on the
  rope dims; softmax scale 1/sqrt(192) times YaRN's mscale squared.
- Router: s = sigmoid(x W) in float32; expert choice on s + bias within
  the `topk_group` of `n_group` groups whose two best biased scores sum
  highest; weights s at the chosen top_k, renormalised, times
  `routed_scaling_factor`.
- Held share: the chip holds experts first .. first + experts_held - 1 of
  each layer; the layer's output is their part of the routed sum plus the
  shared expert. What the other chips' experts add is left out, as in the
  program (a deployment's exchange would bring it).

Departures from the published model, each the benchmark's:
- RoPE in the rotate-half layout. DeepSeek's checkpoints interleave the
  rope dims, a fixed permutation of the columns of wq_b and wkv_a that
  gives the same function on weights drawn at random.
- The correction bias is drawn from the seed (the published one is trained).
- Weights are bfloat16 (the checkpoint holds float8 with 128x128 block
  scales, which a v5e cannot multiply).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import BUCKET, HI, mm, q8, rmsnorm

PAD = 128       # the program pads its vocabulary to a multiple of this


def sizes(c: dict) -> dict:
    """The sizes that run, from a configuration file's published keys (the
    cut ones among them) and its deployment."""
    dep = c["deployment"]
    return {
        "layers": c["num_hidden_layers"],
        "dense_layers": c["first_k_dense_replace"],
        "d_model": c["hidden_size"], "heads": c["num_attention_heads"],
        "q_lora": c["q_lora_rank"], "kv_lora": c["kv_lora_rank"],
        "qk_nope": c["qk_nope_head_dim"], "qk_rope": c["qk_rope_head_dim"],
        "v_head": c["v_head_dim"], "d_ff": c["intermediate_size"],
        "d_ff_expert": c["moe_intermediate_size"],
        "experts": dep["routed_experts"], "experts_held": c["n_routed_experts"],
        "first_expert": dep["first_expert"],
        "shared": c["n_shared_experts"], "top_k": c["num_experts_per_tok"],
        "n_group": c["n_group"], "topk_group": c["topk_group"],
        "norm_topk_prob": c["norm_topk_prob"],
        "routed_scaling_factor": c["routed_scaling_factor"],
        "vocab": c["vocab_size"], "rope_theta": float(c["rope_theta"]),
        "rope_scaling": dict(c["rope_scaling"]),
        "norm_eps": c["rms_norm_eps"],
        "param_dtype": dep["param_dtype"],
        "activation_dtype": dep["activation_dtype"],
    }


def padded_vocab(s: dict) -> int:
    return -(-s["vocab"] // PAD) * PAD


# ---------------------------------------------------------------- weights

def weight_shapes(s: dict) -> Dict[str, tuple]:
    """Flat weights: per layer kind, stacked over its layers."""
    d, H, V = s["d_model"], s["heads"], padded_vocab(s)
    ql, kl, dn, dr, dv = (s["q_lora"], s["kv_lora"], s["qk_nope"],
                          s["qk_rope"], s["v_head"])
    Ld, Lm = s["dense_layers"], s["layers"] - s["dense_layers"]
    E, Eh, fe = s["experts"], s["experts_held"], s["d_ff_expert"]
    fs = fe * s["shared"]
    out = {"embed": (V, d), "lm_head": (d, V), "final_norm": (d,)}
    for kind, L in (("dense", Ld), ("moe", Lm)):
        for name, shape in (("ln_attn", (d,)), ("wq_a", (d, ql)),
                            ("q_norm", (ql,)), ("wq_b", (ql, H, dn + dr)),
                            ("wkv_a", (d, kl + dr)), ("kv_norm", (kl,)),
                            ("wk_b", (kl, H, dn)), ("wv_b", (kl, H, dv)),
                            ("wo", (H, dv, d)), ("ln_mlp", (d,))):
            out[f"{kind}.{name}"] = (L, *shape)
    out.update({"dense.wi": (Ld, d, s["d_ff"]), "dense.wg": (Ld, d, s["d_ff"]),
                "dense.wo_mlp": (Ld, s["d_ff"], d),
                "moe.router": (Lm, d, E), "moe.router_bias": (Lm, E),
                "moe.wi": (Lm, Eh, d, fe), "moe.wg": (Lm, Eh, d, fe),
                "moe.wo_mlp": (Lm, Eh, fe, d),
                "moe.shared_wi": (Lm, d, fs), "moe.shared_wg": (Lm, d, fs),
                "moe.shared_wo": (Lm, fs, d)})
    return out


def _fan_in(name: str, s: dict):
    """Matrices N(0, 1/fan_in); the embedding N(0, 1); the correction bias
    N(0, 0.1^2); norm scales 1 + N(0, 0.1^2)."""
    d, H = s["d_model"], s["heads"]
    base = name.split(".")[-1]
    return {"embed": 1, "lm_head": d, "wq_a": d, "wq_b": s["q_lora"],
            "wkv_a": d, "wk_b": s["kv_lora"], "wv_b": s["kv_lora"],
            "wo": H * s["v_head"], "wi": d, "wg": d,
            "wo_mlp": s["d_ff_expert"] if name.startswith("moe.")
            else s["d_ff"], "router": d, "shared_wi": d, "shared_wg": d,
            "shared_wo": s["d_ff_expert"] * s["shared"]}.get(base)


def make_weights(key, s: dict) -> Dict[str, jax.Array]:
    """Random weights from a key, stored in the configuration's parameter
    dtype, except the norm scales and the correction bias (float32, as the
    program keeps them)."""
    dtype = jnp.dtype(s["param_dtype"])
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(s).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        fan = _fan_in(name, s)
        if fan is not None:
            out[name] = (z / math.sqrt(fan)).astype(dtype)
        elif name.endswith("router_bias"):
            out[name] = 0.1 * z
        else:
            out[name] = 1.0 + 0.1 * z
    return out


# ---------------------------------------------------------- model forward

def yarn_inv_freq(s: dict) -> jax.Array:
    """DeepseekV3YarnRotaryEmbedding's inverse frequencies of the rope
    dims."""
    D, theta, rs = s["qk_rope"], s["rope_theta"], s["rope_scaling"]
    base = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))

    def corr_dim(rot):
        return D * math.log(rs["original_max_position_embeddings"]
                            / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0, 1)
    mask = 1.0 - ramp                 # 1 where the frequency is kept
    inv = base / rs["factor"] * (1.0 - mask) + base * mask
    return jnp.asarray(inv, jnp.float32)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(s: dict) -> float:
    rs = s["rope_scaling"]
    scale = 1.0 / math.sqrt(s["qk_nope"] + s["qk_rope"])
    if rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, pos, s: dict):
    """Rotary embedding with YaRN, rotate-half form: x [T, heads, D]."""
    rs = s["rope_scaling"]
    D = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(s)
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"])
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(x, w, s: dict, low: bool, block: int = 512):
    """Causal multi-head latent attention over a sequence x [T, d]."""
    T, d = x.shape
    H, ql, kl, dn, dr, dv, eps = (s["heads"], s["q_lora"], s["kv_lora"],
                                  s["qk_nope"], s["qk_rope"], s["v_head"],
                                  s["norm_eps"])
    pos = jnp.arange(T)
    q = mm(rmsnorm(mm(x, w["wq_a"], low), w["q_norm"], eps),
           w["wq_b"].reshape(ql, H * (dn + dr)), low).reshape(T, H, dn + dr)
    kv = mm(x, w["wkv_a"], low)
    c = rmsnorm(kv[:, :kl], w["kv_norm"], eps)
    k_rope = rope(kv[:, None, kl:], pos, s)                    # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, s)], -1)
    k = jnp.concatenate([mm(c, w["wk_b"].reshape(kl, H * dn), low).reshape(
        T, H, dn), jnp.broadcast_to(k_rope, (T, H, dr))], -1)
    v = mm(c, w["wv_b"].reshape(kl, H * dv), low).reshape(T, H, dv)
    f = q8 if low else (lambda t: t)
    outs = []
    for b0 in range(0, T, block):       # query blocks keep the logits small
        sc = jnp.einsum("qhd,khd->hqk", f(q[b0:b0 + block]), f(k),
                        precision=HI) * softmax_scale(s)
        mask = pos[None, :] <= pos[b0:b0 + block][:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", f(p), f(v), precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, H * dv)
    return mm(o, w["wo"].reshape(H * dv, d), low)


def route(h, w, s: dict, low: bool = False):
    """The published router over all experts: (weights [T, k], idx [T, k])."""
    T = h.shape[0]
    E, G = s["experts"], s["n_group"]
    scores = jax.nn.sigmoid(mm(h, w["router"], low))
    biased = scores + w["router_bias"]
    per_group = biased.reshape(T, G, E // G)
    group_score = jax.lax.top_k(per_group, 2)[0].sum(-1)
    _, groups = jax.lax.top_k(group_score, s["topk_group"])
    keep = (jnp.arange(G)[None, :, None] == groups[:, None, :]).any(-1)
    biased = jnp.where(jnp.repeat(keep, E // G, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, s["top_k"])
    weights = jnp.take_along_axis(scores, idx, -1)
    if s["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * s["routed_scaling_factor"], idx


def gated(h, wi, wg, wo, low):
    return mm(jax.nn.silu(mm(h, wi, low)) * mm(h, wg, low), wo, low)


def held_moe(h, w, s: dict, low: bool):
    """The held experts' part of the routed sum, plus the shared expert."""
    weights, idx = route(h, w, s, low)
    y = gated(h, w["shared_wi"], w["shared_wg"], w["shared_wo"], low)
    for e in range(s["experts_held"]):
        gate = jnp.where(idx == s["first_expert"] + e, weights, 0.0).sum(-1)
        y = y + gate[:, None] * gated(h, w["wi"][e], w["wg"][e],
                                      w["wo_mlp"][e], low)
    return y


def layer(x, w, s: dict, kind: str, low: bool):
    eps = s["norm_eps"]
    x = x + mla(rmsnorm(x, w["ln_attn"], eps), w, s, low)
    h = rmsnorm(x, w["ln_mlp"], eps)
    if kind == "dense":
        return x + gated(h, w["wi"], w["wg"], w["wo_mlp"], low)
    return x + held_moe(h, w, s, low)


def _hashable(s: dict) -> tuple:
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in s.items()))


@functools.lru_cache(maxsize=None)
def _jitted(key: tuple, low: bool):
    s = {k: dict(v) if k == "rope_scaling" else v for k, v in key}
    layers = {kind: jax.jit(lambda x, lw, kind=kind: layer(x, lw, s, kind,
                                                           low))
              for kind in ("dense", "moe")}
    head = jax.jit(lambda x, fn, lm: mm(rmsnorm(x, fn, s["norm_eps"]), lm,
                                        low))
    return layers, head


def decoder_logits(w: Dict[str, jax.Array], s: dict, tokens: Sequence[int],
                   at: Sequence[int], low: bool = False) -> jax.Array:
    """Logits [len(at), vocab] at positions `at` of the sequence `tokens`,
    one layer at a time with that layer's weights upcast to float32. The
    sequence is padded at its end to a multiple of BUCKET, which the causal
    mask hides from every earlier position."""
    T = len(tokens)
    tok = np.zeros(-(-T // BUCKET) * BUCKET, np.int32)
    tok[:T] = tokens
    layers, head = _jitted(_hashable(s), low)
    x = w["embed"][jnp.asarray(tok)].astype(jnp.float32)
    for li in range(s["layers"]):
        kind = "dense" if li < s["dense_layers"] else "moe"
        i = li if kind == "dense" else li - s["dense_layers"]
        x = layers[kind](x, {n.split(".", 1)[1]: v[i].astype(jnp.float32)
                             for n, v in w.items()
                             if n.startswith(kind + ".")})
    logits = head(x[jnp.asarray(np.asarray(at, np.int32))],
                  w["final_norm"].astype(jnp.float32), w["lm_head"])
    return logits[:, : s["vocab"]]


def served_gaps(w, s: dict, row: List[int], served: List[int],
                low: bool = False) -> np.ndarray:
    """For a request served after the prompt row (as the engine fed it),
    the gap by which each served token's reference logit lies below the
    reference's best at its position. With `low`, the token scored at each
    position is the float8 control's first choice instead."""
    n = len(served)
    seq = list(row) + list(served[:-1])
    at = list(range(len(row) - 1, len(row) - 1 + n))
    ref = decoder_logits(w, s, seq, at)
    pick = (jnp.argmax(decoder_logits(w, s, seq, at, low=True), -1) if low
            else jnp.asarray(np.asarray(served, np.int32)))
    V = ref.shape[-1]
    ok = (pick >= 0) & (pick < V)
    got = jnp.take_along_axis(ref, jnp.clip(pick, 0, V - 1)[:, None],
                              -1)[:, 0]
    return np.asarray(jnp.where(ok, jnp.max(ref, -1) - got, jnp.inf))
