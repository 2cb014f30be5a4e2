"""Attention for the model zoo.

Blocked (flash-style) attention in pure jnp with an *exact static chunk-pair
schedule*: for causal / sliding-window masks we only visit (q-chunk, kv-chunk)
pairs that can contain unmasked entries, so HLO FLOPs match the useful work
(important for the roofline analysis; a naive masked implementation would
double-count causal FLOPs).

Also: GQA grouping, RoPE, MLA (DeepSeek) projections, and single-step decode
attention against a KV cache (the *distributed* seq-sharded decode attention
lives in repro.distributed.decode_attention and reuses the math here).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common
from repro.models.common import ParamBuilder, apply_rope, dense

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Static chunk-pair schedule
# ---------------------------------------------------------------------------


def chunk_pairs(
    nq: int,
    nkv: int,
    cq: int,
    ckv: int,
    kind: str,
    window: int = 0,
    q_offset: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Return static (i, j) chunk-pair arrays that may contain unmasked work.

    kind: "full" | "causal" | "sliding". q_offset shifts absolute q positions
    (kv positions always start at 0).
    """
    pairs = []
    for i in range(nq):
        q_lo = q_offset + i * cq
        q_hi = q_offset + (i + 1) * cq - 1
        for j in range(nkv):
            k_lo = j * ckv
            k_hi = (j + 1) * ckv - 1
            if kind == "full":
                pairs.append((i, j))
                continue
            if k_lo > q_hi:  # strictly future chunk
                continue
            if kind == "sliding" and window > 0 and k_hi < q_lo - window + 1:
                continue  # entirely outside the window of every q in chunk
            pairs.append((i, j))
    if not pairs:
        pairs = [(0, 0)]
    arr = np.asarray(pairs, dtype=np.int32)
    return arr[:, 0], arr[:, 1]


# ---------------------------------------------------------------------------
# Blocked attention (train / prefill)
# ---------------------------------------------------------------------------


def blocked_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, G, D]
    v: jax.Array,  # [B, Skv, G, Dv]
    kind: str = "causal",
    window: int = 0,
    q_offset: int = 0,
    chunk_q: int = 512,
    chunk_kv: int = 512,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
) -> jax.Array:
    """Flash-style blocked attention with online softmax. Returns [B, Sq, H, Dv].

    kind="sliding" attends to positions (t-window, t] (Mistral semantics).
    kv_len masks out padded kv positions >= kv_len.
    """
    B, Sq, H, D = q.shape
    _, Skv, G, _ = k.shape
    Dv = v.shape[-1]
    assert H % G == 0, (H, G)
    R = H // G
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    cq = min(chunk_q, Sq)
    ckv = min(chunk_kv, Skv)
    pad_q = (-Sq) % cq
    pad_kv = (-Skv) % ckv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else k
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0))) if pad_kv else v
    nq, nkv = qp.shape[1] // cq, kp.shape[1] // ckv
    valid_kv = kv_len if kv_len is not None else Skv

    # grouped layouts
    qg = qp.reshape(B, nq, cq, G, R, D)
    kg = kp.reshape(B, nkv, ckv, G, D)
    vg = vp.reshape(B, nkv, ckv, G, Dv)

    ii, jj = chunk_pairs(nq, nkv, cq, ckv, kind, window, q_offset)
    ii = jnp.asarray(ii)
    jj = jnp.asarray(jj)

    acc_dtype = jnp.float32
    m0 = jnp.full((nq, B, cq, G, R), NEG_INF, acc_dtype)
    l0 = jnp.zeros((nq, B, cq, G, R), acc_dtype)
    o0 = jnp.zeros((nq, B, cq, G, R, Dv), acc_dtype)

    def step(carry, idx):
        m, l, o = carry
        i, j = idx
        qi = jax.lax.dynamic_index_in_dim(qg, i, axis=1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kg, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vg, j, axis=1, keepdims=False)
        # logits [B, cq, G, R, ckv] with fp32 accumulation on the MXU
        logits = jnp.einsum(
            "bqgrd,bkgd->bqgrk", qi, kj, preferred_element_type=acc_dtype
        ) * scale
        qpos = q_offset + i * cq + jnp.arange(cq)
        kpos = j * ckv + jnp.arange(ckv)
        mask = kpos[None, :] < valid_kv
        if kind in ("causal", "sliding"):
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if kind == "sliding" and window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)

        mi = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, 0, keepdims=False)
        m_new = jnp.maximum(mi, logits.max(axis=-1))
        corr = jnp.exp(mi - m_new)
        p = jnp.exp(logits - m_new[..., None])
        # guard rows where everything is masked
        p = jnp.where((m_new == NEG_INF)[..., None], 0.0, p)
        l_new = li * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqgrk,bkgd->bqgrd", p.astype(vj.dtype), vj,
                        preferred_element_type=acc_dtype)
        o_new = oi * corr[..., None] + pv
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0)
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, 0)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (ii, jj))
    denom = jnp.where(l == 0.0, 1.0, l)
    out = (o / denom[..., None]).astype(q.dtype)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * cq, H, Dv)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# Decode attention against a KV cache (single step, local math)
# ---------------------------------------------------------------------------


def decode_attend(
    q: jax.Array,            # [B, H, D]
    k_cache: jax.Array,      # [B, Sc, G, D]
    v_cache: jax.Array,      # [B, Sc, G, Dv]
    kv_positions: jax.Array,  # [B, Sc] int32; -1 marks empty slots
    cur_pos: jax.Array,      # [B] int32 position of the query token
    window: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Returns [B, H, Dv]. Also used as the per-shard body of the distributed
    seq-sharded decode (see repro.distributed.decode_attention)."""
    B, H, D = q.shape
    G = k_cache.shape[2]
    R = H // G
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, G, R, D)
    logits = jnp.einsum("bgrd,bkgd->bgrk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = (kv_positions >= 0) & (kv_positions <= cur_pos[:, None])
    if window > 0:
        valid = valid & (kv_positions > cur_pos[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(m == NEG_INF, 0.0, p)
    l = p.sum(axis=-1)
    pv = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
                    preferred_element_type=jnp.float32)
    out = pv / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(B, H, -1).astype(q.dtype)


def decode_attend_partial(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_positions: jax.Array,
    cur_pos: jax.Array,
    window: int = 0,
    scale: Optional[float] = None,
):
    """Partial (un-normalized) decode attention for LSE combining across
    sequence shards: returns (o_partial [B,H,Dv], m [B,H], l [B,H])."""
    B, H, D = q.shape
    G = k_cache.shape[2]
    R = H // G
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, G, R, D)
    logits = jnp.einsum("bgrd,bkgd->bgrk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = (kv_positions >= 0) & (kv_positions <= cur_pos[:, None])
    if window > 0:
        valid = valid & (kv_positions > cur_pos[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where((m == NEG_INF)[..., None], 0.0, p)
    l = p.sum(axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, H, -1), m.reshape(B, H), l.reshape(B, H))


def combine_partials(o, m, l, axis_name: str):
    """LSE-combine flash-decoding partials across a named mesh axis."""
    g_max = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - g_max)
    l_sum = jax.lax.psum(l * corr, axis_name)
    o_sum = jax.lax.psum(o * corr[..., None], axis_name)
    denom = jnp.where(l_sum == 0.0, 1.0, l_sum)
    return o_sum / denom[..., None]


# ---------------------------------------------------------------------------
# Standard GQA attention module
# ---------------------------------------------------------------------------


def init_attention(b: ParamBuilder, cfg, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, G = cfg.num_heads, cfg.num_kv_heads
    b.param("wq", (d, H, hd), ("embed", "heads", "head_dim"), cast=True)
    kv_in_dim = cfg.frontend_dim or d if cross else d
    b.param("wk", (kv_in_dim, G, hd), ("embed", "kv_heads", "head_dim"),
            cast=True)
    b.param("wv", (kv_in_dim, G, hd), ("embed", "kv_heads", "head_dim"),
            cast=True)
    b.param("wo", (H, hd, d), ("heads", "head_dim", "embed"),
            scale=1.0 / math.sqrt(H * hd), cast=True)
    if getattr(cfg, "use_bias", False):
        b.param("bq", (H, hd), ("heads", "head_dim"), init="zeros", cast=True)
        b.param("bv", (G, hd), ("kv_heads", "head_dim"), init="zeros",
                cast=True)
        b.param("bo", (d,), ("embed",), init="zeros", cast=True)
    if cross:
        # Llama-3.2-Vision style tanh gates on cross-attn output
        b.param("gate_attn", (1,), (None,), init="zeros", dtype=jnp.float32)
    if cfg.qk_norm:
        b.param("q_norm_scale", (hd,), ("head_dim",), init="ones", dtype=jnp.float32)
        b.param("k_norm_scale", (hd,), ("head_dim",), init="ones", dtype=jnp.float32)


def _qkv(p, cfg, x, kv_src=None):
    from repro.distributed.act_sharding import constrain
    kv_src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dgk->bsgk", kv_src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dgk->bsgk", kv_src, p["wv"].astype(x.dtype))
    q = constrain(q, "dp", None, "tp", None)
    k = constrain(k, "dp", None, None, None)
    v = constrain(v, "dp", None, None, None)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm_scale"])
        k = _rms_head(k, p["k_norm_scale"])
    return q, k, v


def _rms_head(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _out_proj(p, o):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(o.dtype)
    return y


def attention_forward(
    p,
    cfg,
    x: jax.Array,           # [B, S, d]
    positions: jax.Array,   # [S] absolute positions
    kind: Optional[str] = None,
    window: Optional[int] = None,
    kv_src: Optional[jax.Array] = None,  # cross-attention source
) -> jax.Array:
    cross = kv_src is not None
    q, k, v = _qkv(p, cfg, x, kv_src)
    if cfg.use_rope and not cross:
        # q,k are [B,S,H,D]: rope over S with head axis trailing
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kind is None:
        kind = {"full": "causal", "sliding": "sliding", "local": "sliding"}[
            cfg.attention_kind]
        window = cfg.sliding_window if cfg.attention_kind == "sliding" else (
            cfg.local_window if cfg.attention_kind == "local" else 0)
    window = window or 0
    o = blocked_attention(q, k, v, kind=kind, window=window)
    y = _out_proj(p, o)
    if cross and "gate_attn" in p:
        y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
    return y


def attention_prefill(p, cfg, x, positions, cache_len: int,
                      kind: Optional[str] = None, window: Optional[int] = None):
    """Forward + return (output, cache dict) holding the last cache_len tokens."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if kind is None:
        kind = {"full": "causal", "sliding": "sliding", "local": "sliding"}[
            cfg.attention_kind]
        window = cfg.sliding_window if cfg.attention_kind == "sliding" else (
            cfg.local_window if cfg.attention_kind == "local" else 0)
    window = window or 0
    o = blocked_attention(q, k, v, kind=kind, window=window)
    y = _out_proj(p, o)
    # build cache from the last cache_len tokens (ring base state)
    take = min(cache_len, S)
    pad = cache_len - take
    k_c = jnp.pad(k[:, S - take:], ((0, 0), (0, pad), (0, 0), (0, 0)))
    v_c = jnp.pad(v[:, S - take:], ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos_slice = positions[S - take:]
    pos_c = jnp.broadcast_to(
        jnp.pad(pos_slice, (0, pad), constant_values=-1), (B, cache_len)
    ).astype(jnp.int32)
    cache = {"k": k_c, "v": v_c, "pos": pos_c}
    return y, cache


def attention_decode(p, cfg, x, cache, cur_pos,
                     kind: Optional[str] = None, window: Optional[int] = None,
                     attend_fn=None):
    """One-token decode. x: [B, 1, d]; cache k/v: [B, Sc, G, D], pos [B, Sc];
    cur_pos [B]. Writes the new token at slot cur_pos % Sc (ring semantics).
    attend_fn lets the distributed runtime substitute seq-sharded attention."""
    B = x.shape[0]
    Sc = cache["k"].shape[1]
    q, k, v = _qkv(p, cfg, x)
    if cfg.use_rope:
        pos2 = cur_pos[:, None]  # [B,1]
        q = apply_rope(q, pos2, cfg.rope_theta)
        k = apply_rope(k, pos2, cfg.rope_theta)
    slot = (cur_pos % Sc).astype(jnp.int32)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    pos_cache = cache["pos"].at[bidx, slot].set(cur_pos.astype(jnp.int32))
    if window is None:
        window = cfg.sliding_window if cfg.attention_kind == "sliding" else (
            cfg.local_window if cfg.attention_kind == "local" else 0)
    fn = attend_fn or decode_attend
    o = fn(q[:, 0], k_cache, v_cache, pos_cache, cur_pos, window=window)
    y = _out_proj(p, o[:, None])
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    return y, new_cache


def cross_attention_decode(p, cfg, x, cache):
    """Decode-time cross attention against static (precomputed) cross KV."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    B = x.shape[0]
    Sc = cache["k"].shape[1]
    pos = jnp.broadcast_to(jnp.arange(Sc, dtype=jnp.int32), (B, Sc))
    o = decode_attend(q[:, 0], cache["k"], cache["v"], pos,
                      jnp.full((B,), Sc, jnp.int32))
    y = _out_proj(p, o[:, None])
    if "gate_attn" in p:
        y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
    return y


def cross_attention_build_cache(p, cfg, kv_src):
    k = jnp.einsum("bsd,dgk->bsgk", kv_src, p["wk"].astype(kv_src.dtype))
    v = jnp.einsum("bsd,dgk->bsgk", kv_src, p["wv"].astype(kv_src.dtype))
    if "bv" in p:
        v = v + p["bv"].astype(kv_src.dtype)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 Multi-head Latent Attention)
# ---------------------------------------------------------------------------


def init_mla(b: ParamBuilder, cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    b.param("wq_a", (d, m.q_lora_rank), ("embed", None), cast=True)
    b.param("q_norm", (m.q_lora_rank,), (None,), init="ones", dtype=jnp.float32)
    b.param("wq_b", (m.q_lora_rank, H, dn + dr), (None, "heads", "head_dim"),
            cast=True)
    b.param("wkv_a", (d, m.kv_lora_rank + dr), ("embed", None), cast=True)
    b.param("kv_norm", (m.kv_lora_rank,), (None,), init="ones", dtype=jnp.float32)
    b.param("wk_b", (m.kv_lora_rank, H, dn), (None, "heads", "head_dim"),
            cast=True)
    b.param("wv_b", (m.kv_lora_rank, H, dv), (None, "heads", "head_dim"),
            cast=True)
    b.param("wo", (H, dv, d), ("heads", "head_dim", "embed"),
            scale=1.0 / math.sqrt(H * dv), cast=True)


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def mla_latents(p, cfg, x, positions):
    """Compute q (nope+rope), compressed kv latent, and rope key."""
    m = cfg.mla
    dn = m.qk_nope_head_dim
    q_lat = _rms(dense(p["wq_a"], x), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    kv = dense(p["wkv_a"], x)
    c_kv = _rms(kv[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., m.kv_lora_rank:][:, :, None, :]  # [B,S,1,dr] shared head
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope, c_kv, k_rope


def mla_scale(cfg) -> float:
    """Softmax scale: 1/sqrt(qk head dim), times YaRN's mscale squared
    where `rope_scaling` sets `mscale_all_dim` (DeepseekV3Attention)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= common.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_attend(p, cfg, x, q_nope, q_rope, c_kv, k_rope):
    """Train/prefill attention: reconstruct per-head K,V from the latent
    (the non-absorbed form, cheaper for long sequences), then blocked
    attention and the output projection."""
    m = cfg.mla
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"].astype(x.dtype))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    o = blocked_attention(q_full, k_full, v, kind="causal",
                          scale=mla_scale(cfg))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


@jax.named_scope("mla")
def mla_forward(p, cfg, x, positions):
    return _mla_attend(p, cfg, x, *mla_latents(p, cfg, x, positions))


@jax.named_scope("mla")
def mla_prefill(p, cfg, x, positions, cache_len: int):
    q_nope, q_rope, c_kv, k_rope = mla_latents(p, cfg, x, positions)
    y = _mla_attend(p, cfg, x, q_nope, q_rope, c_kv, k_rope)
    # latent cache: c_kv + rope key (per-token 576 values for dsv3)
    B, S = x.shape[:2]
    take = min(cache_len, S)
    pad = cache_len - take
    c = jnp.pad(c_kv[:, S - take:], ((0, 0), (0, pad), (0, 0)))
    kr = jnp.pad(k_rope[:, S - take:, 0], ((0, 0), (0, pad), (0, 0)))
    pos_c = jnp.broadcast_to(
        jnp.pad(positions[S - take:], (0, pad), constant_values=-1), (B, cache_len)
    ).astype(jnp.int32)
    return y, {"c_kv": c, "k_rope": kr, "pos": pos_c}


@jax.named_scope("mla")
def mla_decode(p, cfg, x, cache, cur_pos):
    """Absorbed-form decode: score against the latent cache directly."""
    B = x.shape[0]
    Sc = cache["c_kv"].shape[1]
    q_nope, q_rope, c_kv_new, k_rope_new = mla_latents(
        p, cfg, x, cur_pos[:, None])
    slot = (cur_pos % Sc).astype(jnp.int32)
    bidx = jnp.arange(B)
    c_cache = cache["c_kv"].at[bidx, slot].set(
        c_kv_new[:, 0].astype(cache["c_kv"].dtype))
    r_cache = cache["k_rope"].at[bidx, slot].set(
        k_rope_new[:, 0, 0].astype(cache["k_rope"].dtype))
    pos_cache = cache["pos"].at[bidx, slot].set(cur_pos.astype(jnp.int32))

    # absorb: q_eff[b,h,r] = q_nope . wk_b   -> score against latent
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wk_b"].astype(x.dtype))
    logits = (
        jnp.einsum("bhr,bsr->bhs", q_abs, c_cache,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], r_cache,
                     preferred_element_type=jnp.float32)
    ) * mla_scale(cfg)
    valid = (pos_cache >= 0) & (pos_cache <= cur_pos[:, None])
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    mmax = logits.max(axis=-1, keepdims=True)
    pr = jnp.exp(logits - mmax)
    pr = pr / pr.sum(axis=-1, keepdims=True)
    ctx_lat = jnp.einsum("bhs,bsr->bhr", pr.astype(c_cache.dtype), c_cache,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bhr,rhk->bhk", ctx_lat, p["wv_b"].astype(x.dtype))
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(o.dtype))[:, None]
    return y, {"c_kv": c_cache, "k_rope": r_cache, "pos": pos_cache}
