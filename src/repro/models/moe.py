"""Mixture-of-Experts layer (DBRX-style top-k, DeepSeek-V3 shared+routed).

Routing (`_router`): softmax top-k (DBRX), or DeepSeek-V3's sigmoid scores
with a correction bias, limited to the best expert groups (`MoEConfig`).

Implementations:
  - "scatter" (default): capacity-based dispatch via gather/scatter. HLO FLOPs
    are proportional to *active* expert compute (honest for roofline); XLA
    GSPMD chooses the collectives. The hand-optimized expert-parallel
    shard_map path lives in repro.distributed (perf iteration).
  - "held" (taken when `MoEConfig.experts_held` is set): the layer of one
    chip of an expert-parallel deployment. It routes over all experts and
    computes the part of the output that its held experts give, dropless
    (`held_experts_ffn`, the function the shard_map path runs per shard),
    plus the shared expert. Nothing stands in for the absent experts.
  - "dense_mask": every expert computes every token, masked combine. Used as a
    correctness oracle in tests (no capacity drops when cf is large).

Named scopes `moe.router`, `moe.experts` and `moe.shared` mark the ops of
each part in the HLO metadata (`op_name`).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamBuilder, activation


def init_moe(b: ParamBuilder, cfg):
    mo = cfg.moe
    d = cfg.d_model
    c = b.child("moe")
    c.param("router", (d, mo.num_experts), ("embed", "experts"),
            scale=1.0 / math.sqrt(d))
    if mo.scoring == "sigmoid":     # noaux_tc correction bias (trained)
        c.param("router_bias", (mo.num_experts,), ("experts",), init="zeros",
                dtype=jnp.float32)
    ff = mo.d_ff_expert
    E = mo.experts_held or mo.num_experts
    c.param("wi", (E, d, ff), ("experts", "embed", "expert_mlp"), cast=True)
    if cfg.use_glu:
        c.param("wg", (E, d, ff), ("experts", "embed", "expert_mlp"),
                cast=True)
    c.param("wo", (E, ff, d), ("experts", "expert_mlp", "embed"), cast=True)
    if mo.num_shared_experts > 0:
        ffs = (mo.d_ff_shared or ff) * mo.num_shared_experts
        c.param("shared_wi", (d, ffs), ("embed", "mlp"), cast=True)
        if cfg.use_glu:
            c.param("shared_wg", (d, ffs), ("embed", "mlp"), cast=True)
        c.param("shared_wo", (ffs, d), ("mlp", "embed"), cast=True)


@jax.named_scope("moe.router")
def _router(p, cfg, x_flat):
    """Top-k routing over all experts. Returns (weights [T,k] f32,
    idx [T,k], aux_loss scalar)."""
    mo = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if mo.scoring == "sigmoid":
        return (*_group_limited_top_k(mo, jax.nn.sigmoid(logits),
                                      p["router_bias"]),
                jnp.zeros((), jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, mo.top_k)
    weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss: E * sum_e f_e * P_e
    E = mo.num_experts
    f = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    P = probs.mean(axis=0)
    aux = E * jnp.sum(f * P) * mo.aux_loss_coef
    return weights, idx, aux


def _group_limited_top_k(mo, scores, bias):
    """DeepSeek-V3's noaux_tc choice (MoEGate): experts are chosen by
    score + bias, only within the `topk_group` groups whose two best biased
    scores sum highest; the weights are the unbiased scores of the chosen,
    renormalised (DeepSeek-V3's `norm_topk_prob` is true)."""
    T, E = scores.shape
    choice = scores + bias.astype(jnp.float32)
    groups = choice.reshape(T, mo.n_group, E // mo.n_group)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
    _, top_groups = jax.lax.top_k(group_score, mo.topk_group)
    in_top = jnp.zeros((T, mo.n_group), bool).at[
        jnp.arange(T)[:, None], top_groups].set(True)
    choice = jnp.where(jnp.repeat(in_top, E // mo.n_group, axis=1), choice,
                       -jnp.inf)
    _, idx = jax.lax.top_k(choice, mo.top_k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * mo.routed_scaling_factor, idx


@jax.named_scope("moe.experts")
def held_experts_ffn(cfg, xf, weights, idx, wi, wg, wo, first,
                     capacity: int) -> jax.Array:
    """The part of the routed output [T, d] that experts first .. first +
    E_loc - 1 give, whose weights wi/wg/wo [E_loc, ...] are held here, for
    tokens xf [T, d] with global routing (weights, idx [T, k]). Each expert
    takes its assignments in token order up to `capacity` and drops the
    rest; `capacity` T is dropless, since a token picks an expert at most
    once. Shapes are static: E_loc x capacity rows are computed."""
    T, d = xf.shape
    k = idx.shape[1]
    E_loc, C = wi.shape[0], capacity
    a = idx.reshape(T * k) - first
    held = (a >= 0) & (a < E_loc)
    a = jnp.where(held, a, 0)
    onehot = jax.nn.one_hot(a, E_loc, dtype=jnp.int32) * held[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot          # exclusive cumsum
    pos = jnp.take_along_axis(pos, a[:, None], axis=1)[:, 0]
    keep = held & (pos < C)
    slot = jnp.where(keep, a * C + pos, E_loc * C)     # E_loc*C: the drop slot
    # each slot's token and combine weight (an empty slot: token 0, weight 0)
    tok = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot].set(
        jnp.arange(T * k, dtype=jnp.int32) // k)[:-1]
    w = jnp.zeros((E_loc * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, weights.reshape(T * k).astype(jnp.float32), 0.0))[:-1]
    expert_in = xf[tok].reshape(E_loc, C, d)
    act = activation(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(xf.dtype))
    if wg is not None:
        h = act(h) * jnp.einsum("ecd,edf->ecf", expert_in,
                                wg.astype(xf.dtype))
    else:
        h = act(h)
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(xf.dtype))
    out = out.reshape(E_loc * C, d).astype(jnp.float32) * w[:, None]
    return jnp.zeros((T, d), jnp.float32).at[tok].add(out).astype(xf.dtype)


@jax.named_scope("moe.experts")
def _expert_ffn(p, cfg, h_in):
    """h_in: [E, C, d] -> [E, C, d]."""
    act = activation(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", h_in, p["wi"].astype(h_in.dtype))
    if cfg.use_glu:
        h = act(h) * jnp.einsum("ecd,edf->ecf", h_in, p["wg"].astype(h_in.dtype))
    else:
        h = act(h)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(h_in.dtype))


@jax.named_scope("moe.shared")
def _shared_ffn(p, cfg, x):
    act = activation(cfg.act)
    h = jnp.einsum("td,df->tf", x, p["shared_wi"].astype(x.dtype))
    if cfg.use_glu:
        h = act(h) * jnp.einsum("td,df->tf", x, p["shared_wg"].astype(x.dtype))
    else:
        h = act(h)
    return jnp.einsum("tf,fd->td", h, p["shared_wo"].astype(x.dtype))


def moe_forward_scatter(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss). Capacity-based scatter dispatch."""
    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    weights, idx, aux = _router(p, cfg, xf)

    E, k = mo.num_experts, mo.top_k
    C = max(1, int(math.ceil(k * T * mo.capacity_factor / E)))
    # assignment-major order: token t rank r -> row t*k + r
    a = idx.reshape(T * k)
    onehot = jax.nn.one_hot(a, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos_in_expert = jnp.take_along_axis(pos, a[:, None], axis=1)[:, 0]
    keep = pos_in_expert < C
    dest = jnp.where(keep, a * C + pos_in_expert, E * C)  # E*C = drop slot

    from repro.distributed.act_sharding import constrain, current
    x_rep = jnp.repeat(xf, k, axis=0)  # [T*k, d] token-major
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].add(
        x_rep * keep[:, None].astype(x.dtype))
    expert_in = buf[: E * C].reshape(E, C, d)
    h = current()
    if h is not None and getattr(h, "moe_expert_parallel", False):
        expert_in = constrain(expert_in, "tp", None, None)  # expert-parallel
    expert_out = _expert_ffn(p, cfg, expert_in).reshape(E * C, d)
    expert_out = jnp.concatenate(
        [expert_out, jnp.zeros((1, d), expert_out.dtype)], axis=0)

    gathered = expert_out[dest] * (
        weights.reshape(T * k, 1).astype(x.dtype) * keep[:, None].astype(x.dtype))
    y = gathered.reshape(T, k, d).sum(axis=1)
    if mo.num_shared_experts > 0:
        y = y + _shared_ffn(p, cfg, xf)
    return y.reshape(B, S, d), aux


def moe_forward_dense(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Oracle: all experts compute all tokens; combine with routing weights."""
    mo = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    weights, idx, aux = _router(p, cfg, xf)
    # combine weights as dense [T, E]
    w_dense = jnp.zeros((T, mo.num_experts), x.dtype)
    w_dense = w_dense.at[jnp.arange(T)[:, None], idx].set(weights.astype(x.dtype))
    E_loc = p["wi"].shape[0]       # the held experts' columns only
    w_dense = w_dense[:, mo.first_expert: mo.first_expert + E_loc]
    all_in = jnp.broadcast_to(xf[None], (E_loc, T, d))
    all_out = _expert_ffn(p, cfg, all_in)  # [E, T, d]
    y = jnp.einsum("etd,te->td", all_out, w_dense)
    if mo.num_shared_experts > 0:
        y = y + _shared_ffn(p, cfg, xf)
    return y.reshape(B, S, d), aux


def moe_forward_held(p, cfg, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss): the held experts' part, dropless,
    plus the shared expert."""
    mo = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    weights, idx, aux = _router(p, cfg, xf)
    y = held_experts_ffn(cfg, xf, weights, idx, p["wi"], p.get("wg"),
                         p["wo"], mo.first_expert, B * S)
    if mo.num_shared_experts > 0:
        y = y + _shared_ffn(p, cfg, xf)
    return y.reshape(B, S, d), aux


def moe_forward(p, cfg, x, impl: str = "scatter"):
    from repro.distributed.act_sharding import current
    if impl == "scatter" and cfg.moe.experts_held:
        return moe_forward_held(p, cfg, x)
    h = current()
    if impl == "scatter" and h is not None and \
            getattr(h, "moe_impl", None) == "expert_parallel":
        impl = "expert_parallel"
    if impl == "expert_parallel" and h is not None:
        from repro.distributed.expert_parallel import \
            moe_forward_expert_parallel
        return moe_forward_expert_parallel(p, cfg, x, h)
    if impl in ("scatter", "expert_parallel"):
        return moe_forward_scatter(p, cfg, x)
    if impl == "dense_mask":
        return moe_forward_dense(p, cfg, x)
    raise ValueError(impl)
