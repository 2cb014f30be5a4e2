"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

arXiv:2405.04517. mLSTM recurrent form (per head, keys scaled by 1/sqrt(d)):
  m_t = max(log f_t + m_{t-1}, i~_t)
  i'  = exp(i~_t - m_t);  f' = exp(log f_t + m_{t-1} - m_t)
  C_t = f' C_{t-1} + i' v_t k_t^T ;  n_t = f' n_{t-1} + i' k_t
  h~_t = C_t q_t / max(|n_t . q_t|, exp(-m_t))

Train/prefill uses the *chunkwise-parallel* form (intra-chunk quadratic +
inter-chunk recurrence) — the TPU-native formulation and the reference for the
Pallas kernel. Decode uses the exact recurrent step. sLSTM is a strictly
sequential scalar recurrence (lax.scan) with exponential gating + stabilizer.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamBuilder
from repro.models.recurrent import conv1d_causal, conv1d_decode, init_conv1d

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# mLSTM core
# ---------------------------------------------------------------------------


def mlstm_recurrent(q, k, v, i_gate, f_gate, state=None):
    """Exact sequential reference / decode path.

    q,k,v: [B, S, H, D]; i_gate,f_gate: [B, S, H] (pre-activation).
    state: (C [B,H,D,D], n [B,H,D], m [B,H]) or None.
    Returns (h [B,S,H,D], state).
    """
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    if state is None:
        C = jnp.zeros((B, H, D, D), jnp.float32)
        n = jnp.zeros((B, H, D), jnp.float32)
        m = jnp.full((B, H), -jnp.inf, jnp.float32)
        state = (C, n, m)

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, it, ft = inp  # [B,H,D], [B,H]
        kt = kt.astype(jnp.float32) * scale
        vt = vt.astype(jnp.float32)
        qt = qt.astype(jnp.float32)
        logf = jax.nn.log_sigmoid(ft.astype(jnp.float32))
        m_new = jnp.maximum(logf + m, it.astype(jnp.float32))
        i_p = jnp.exp(it.astype(jnp.float32) - m_new)
        f_p = jnp.exp(logf + m - m_new)
        C = f_p[..., None, None] * C + i_p[..., None, None] * (
            vt[..., :, None] * kt[..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * kt
        num = jnp.einsum("bhvk,bhk->bhv", C, qt)
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, qt))
        den = jnp.maximum(den, jnp.exp(-m_new))
        h = num / den[..., None]
        return (C, n, m_new), h

    xs = (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
          i_gate.swapaxes(0, 1), f_gate.swapaxes(0, 1))
    state, hs = jax.lax.scan(step, state, xs)
    return hs.swapaxes(0, 1).astype(q.dtype), state


def mlstm_chunkwise(q, k, v, i_gate, f_gate, chunk: int = 256, state=None):
    """Chunkwise-parallel mLSTM. Same I/O contract as mlstm_recurrent."""
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    pad = (-S) % chunk
    if pad:
        zpad = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v = zpad(q), zpad(k), zpad(v)
        i_gate = zpad(i_gate)
        # padded forget gates -> large positive (f=1, carries state through)
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)), constant_values=30.0)
        # padded input gates -> very negative (no contribution)
        i_gate = i_gate.at[:, S:].set(NEG_INF) if pad else i_gate
    Sp = q.shape[1]
    NC = Sp // chunk
    L = chunk

    def resh(x):
        return x.reshape(B, NC, L, *x.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = resh(q), resh(k), resh(v)          # [NC, B, L, H, D]
    ic, fc = resh(i_gate), resh(f_gate)              # [NC, B, L, H]

    if state is None:
        C0 = jnp.zeros((B, H, D, D), jnp.float32)
        n0 = jnp.zeros((B, H, D), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
        state = (C0, n0, m0)

    tri = jnp.tril(jnp.ones((L, L), bool))            # s <= t
    tri_strict = jnp.tril(jnp.ones((L, L), bool), -1)

    def chunk_step(carry, inp):
        C, n, m_c = carry
        qt, kt, vt, it, ft = inp
        qt = qt.astype(jnp.float32)
        kt = kt.astype(jnp.float32) * scale
        vt = vt.astype(jnp.float32)
        it = it.astype(jnp.float32)        # [B, L, H]
        logf = jax.nn.log_sigmoid(ft.astype(jnp.float32))
        b = jnp.cumsum(logf, axis=1)       # inclusive cumsum  [B, L, H]
        B_tot = b[:, -1]                   # [B, H]

        # per-query stabilizers
        # intra: max_{s<=t} (b_t - b_s + i_s)  (s=t term: i_t)
        g = it - b                          # [B, L, H] (i_s - b_s)
        # running max over s<=t of g, then + b_t
        g_run = jax.lax.cummax(g, axis=1)
        m_intra = b + g_run                 # [B, L, H]
        m_inter = b + m_c[:, None, :]       # [B, L, H]
        m_q = jnp.maximum(m_intra, m_inter)

        # inter-chunk contribution (state carries implicit exp(-m_c))
        q_h = qt.swapaxes(1, 2)             # [B, H, L, D]
        inter_scale = jnp.exp(m_inter - m_q).swapaxes(1, 2)  # [B, H, L]
        # C is [B,H,Dv,Dk]; contract q over Dk: num = C q
        num_inter = jnp.einsum("bhvk,bhlk->bhlv", C, q_h) * inter_scale[..., None]
        den_inter = jnp.einsum("bhk,bhlk->bhl", n, q_h) * inter_scale

        # intra-chunk quadratic part
        # D~_ts = b_t - b_s + i_s for s <= t, else -inf ; weight exp(D~ - m_q)
        dmat = (b[:, :, None, :] - b[:, None, :, :] + it[:, None, :, :])
        dmat = jnp.where(tri[None, :, :, None], dmat, NEG_INF)
        w = jnp.exp(dmat - m_q[:, :, None, :])       # [B, T, S, H]
        scores = jnp.einsum("bthd,bshd->btsh", qt, kt) * w
        num_intra = jnp.einsum("btsh,bshv->bthv", scores, vt)   # [B,L,H,Dv]
        den_intra = scores.sum(axis=2)               # [B, L, H]

        num = num_inter.transpose(0, 2, 1, 3) + num_intra
        den = den_inter.transpose(0, 2, 1) + den_intra
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_q))
        h = num / den[..., None]

        # state update to end of chunk
        m_next = jnp.maximum(
            B_tot + m_c,
            (B_tot[:, :, None] + g.swapaxes(1, 2)).max(axis=-1))
        # decay factors for each source position s: exp(B_tot - b_s + i_s - m_next)
        s_decay = jnp.exp(B_tot[:, None, :] - b + it - m_next[:, None, :])
        s_decay = s_decay.swapaxes(1, 2)             # [B, H, L]
        k_h = kt.transpose(0, 2, 1, 3)               # [B, H, L, D]
        v_h = vt.transpose(0, 2, 1, 3)
        C_new = C * jnp.exp(B_tot + m_c - m_next)[..., None, None] + jnp.einsum(
            "bhl,bhlv,bhlk->bhvk", s_decay, v_h, k_h)
        n_new = n * jnp.exp(B_tot + m_c - m_next)[..., None] + jnp.einsum(
            "bhl,bhlk->bhk", s_decay, k_h)
        return (C_new, n_new, m_next), h

    state, hs = jax.lax.scan(chunk_step, state, (qc, kc, vc, ic, fc))
    h = hs.swapaxes(0, 1).reshape(B, Sp, H, D)[:, :S]
    return h.astype(q.dtype), state


def mlstm_step(q1, k1, v1, i1, f1, state):
    """Single-token decode. q1..: [B, H, D], gates [B, H]."""
    h, state = mlstm_recurrent(q1[:, None], k1[:, None], v1[:, None],
                               i1[:, None], f1[:, None], state)
    return h[:, 0], state


# ---------------------------------------------------------------------------
# mLSTM block (pre-LN, up-proj x2, conv4, heads, output gate via silu branch)
# ---------------------------------------------------------------------------


def init_mlstm_block(b: ParamBuilder, cfg):
    d = cfg.d_model
    inner = 2 * d
    nh = cfg.num_heads
    b.param("w_up", (d, inner), ("embed", "mlp"), cast=True)
    b.param("w_gate", (d, inner), ("embed", "mlp"), cast=True)
    init_conv1d(b, "conv", cfg.conv_width, inner)
    for name in ("wq", "wk", "wv"):
        b.param(name, (inner, inner), ("mlp", "mlp2"),
                scale=1.0 / math.sqrt(inner), cast=True)
    b.param("w_if", (inner, 2 * nh), ("mlp", None),
            scale=1.0 / math.sqrt(inner), cast=True)
    b.param("b_if", (2 * nh,), (None,), init="zeros", cast=True)
    b.param("skip_scale", (inner,), ("mlp",), init="ones", cast=True)
    b.param("w_down", (inner, d), ("mlp", "embed"), cast=True)


def _mlstm_qkvif(p, cfg, u):
    """u: [B, S, inner] (post-up-proj). Returns q,k,v [B,S,H,D], gates [B,S,H]."""
    nh = cfg.num_heads
    c = conv1d_causal(p["conv"], u)
    c_act = jax.nn.silu(c)
    q = jnp.einsum("bsi,ij->bsj", c_act, p["wq"].astype(u.dtype))
    k = jnp.einsum("bsi,ij->bsj", c_act, p["wk"].astype(u.dtype))
    v = jnp.einsum("bsi,ij->bsj", u, p["wv"].astype(u.dtype))
    gates = jnp.einsum("bsi,ij->bsj", c_act, p["w_if"].astype(u.dtype)) + \
        p["b_if"].astype(u.dtype)
    B, S, inner = u.shape
    D = inner // nh
    q = q.reshape(B, S, nh, D)
    k = k.reshape(B, S, nh, D)
    v = v.reshape(B, S, nh, D)
    i_gate, f_gate = gates[..., :nh], gates[..., nh:]
    return q, k, v, i_gate, f_gate, c_act


def mlstm_block_forward(p, cfg, x, chunk: int = 256):
    from repro.distributed.act_sharding import constrain
    B, S, d = x.shape
    u = jnp.einsum("bsd,di->bsi", x, p["w_up"].astype(x.dtype))
    g = jnp.einsum("bsd,di->bsi", x, p["w_gate"].astype(x.dtype))
    u = constrain(u, "dp", None, "tp")
    g = constrain(g, "dp", None, "tp")
    q, k, v, ig, fg, c_act = _mlstm_qkvif(p, cfg, u)
    h, _ = mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk)
    h = h.reshape(B, S, -1) + p["skip_scale"].astype(x.dtype) * c_act
    y = h * jax.nn.silu(g)
    return jnp.einsum("bsi,id->bsd", y, p["w_down"].astype(x.dtype))


def mlstm_block_prefill(p, cfg, x, chunk: int = 256):
    B, S, d = x.shape
    u = jnp.einsum("bsd,di->bsi", x, p["w_up"].astype(x.dtype))
    g = jnp.einsum("bsd,di->bsi", x, p["w_gate"].astype(x.dtype))
    q, k, v, ig, fg, c_act = _mlstm_qkvif(p, cfg, u)
    h, state = mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk)
    h = h.reshape(B, S, -1) + p["skip_scale"].astype(x.dtype) * c_act
    y = h * jax.nn.silu(g)
    out = jnp.einsum("bsi,id->bsd", y, p["w_down"].astype(x.dtype))
    cw = cfg.conv_width
    conv_state = u[:, -(cw - 1):] if cw > 1 else u[:, :0]
    return out, {"C": state[0], "n": state[1], "m": state[2],
                 "conv": conv_state}


def mlstm_block_decode(p, cfg, x_t, st):
    """x_t: [B, 1, d]."""
    nh = cfg.num_heads
    xt = x_t[:, 0]
    u = jnp.einsum("bd,di->bi", xt, p["w_up"].astype(xt.dtype))
    g = jnp.einsum("bd,di->bi", xt, p["w_gate"].astype(xt.dtype))
    c, conv_state = conv1d_decode(p["conv"], u, st["conv"])
    c_act = jax.nn.silu(c)
    q = jnp.einsum("bi,ij->bj", c_act, p["wq"].astype(xt.dtype))
    k = jnp.einsum("bi,ij->bj", c_act, p["wk"].astype(xt.dtype))
    v = jnp.einsum("bi,ij->bj", u, p["wv"].astype(xt.dtype))
    gates = jnp.einsum("bi,ij->bj", c_act, p["w_if"].astype(xt.dtype)) + \
        p["b_if"].astype(xt.dtype)
    B = xt.shape[0]
    inner = u.shape[-1]
    D = inner // nh
    h, state = mlstm_step(
        q.reshape(B, nh, D), k.reshape(B, nh, D), v.reshape(B, nh, D),
        gates[..., :nh], gates[..., nh:], (st["C"], st["n"], st["m"]))
    h = h.reshape(B, -1) + p["skip_scale"].astype(xt.dtype) * c_act
    y = h * jax.nn.silu(g)
    out = jnp.einsum("bi,id->bd", y, p["w_down"].astype(xt.dtype))
    return out[:, None], {"C": state[0], "n": state[1], "m": state[2],
                          "conv": conv_state}


# ---------------------------------------------------------------------------
# sLSTM block (scalar memory, block-diagonal per-head recurrence)
# ---------------------------------------------------------------------------


def init_slstm_block(b: ParamBuilder, cfg):
    d = cfg.d_model
    nh = cfg.num_heads
    dh = d // nh
    init_conv1d(b, "conv", cfg.conv_width, d)
    for gate in ("z", "i", "f", "o"):
        b.param(f"w_{gate}", (d, d), ("embed", "mlp"),
                scale=1.0 / math.sqrt(d), cast=True)
        b.param(f"r_{gate}", (nh, dh, dh), ("heads", None, None),
                scale=1.0 / math.sqrt(dh))
        b.param(f"b_{gate}", (d,), ("mlp",), init="zeros", cast=True)
    # post-up-projection FFN (factor 4/3, GeGLU per paper)
    ff = int(d * 4 / 3)
    b.param("ffn_norm_scale", (d,), ("embed",), init="ones", dtype=jnp.float32)
    b.param("ffn_wi", (d, ff), ("embed", "mlp"), cast=True)
    b.param("ffn_wg", (d, ff), ("embed", "mlp"), cast=True)
    b.param("ffn_wo", (ff, d), ("mlp", "embed"), cast=True)


def slstm_scan(p, cfg, x_conv, x_raw, state=None):
    """x_conv: conv-smoothed input (for i/f gates), x_raw for z/o. [B,S,d]."""
    B, S, d = x_raw.shape
    nh = cfg.num_heads
    dh = d // nh

    wz = p["w_z"].astype(x_raw.dtype)
    wi = p["w_i"].astype(x_raw.dtype)
    wf = p["w_f"].astype(x_raw.dtype)
    wo = p["w_o"].astype(x_raw.dtype)
    # input contributions precomputed for the whole sequence
    zx = jnp.einsum("bsd,de->bse", x_raw, wz) + p["b_z"].astype(x_raw.dtype)
    ix = jnp.einsum("bsd,de->bse", x_conv, wi) + p["b_i"].astype(x_raw.dtype)
    fx = jnp.einsum("bsd,de->bse", x_conv, wf) + p["b_f"].astype(x_raw.dtype)
    ox = jnp.einsum("bsd,de->bse", x_raw, wo) + p["b_o"].astype(x_raw.dtype)

    if state is None:
        c0 = jnp.zeros((B, d), jnp.float32)
        n0 = jnp.ones((B, d), jnp.float32)
        h0 = jnp.zeros((B, d), jnp.float32)
        m0 = jnp.zeros((B, d), jnp.float32)
        state = (c0, n0, h0, m0)

    rz = p["r_z"].astype(jnp.float32)
    ri = p["r_i"].astype(jnp.float32)
    rf = p["r_f"].astype(jnp.float32)
    ro = p["r_o"].astype(jnp.float32)

    def rec(r, h):
        hh = h.reshape(B, nh, dh)
        return jnp.einsum("bhk,hkj->bhj", hh, r).reshape(B, d)

    def step(carry, inp):
        c, n, h, m = carry
        zx_t, ix_t, fx_t, ox_t = [t.astype(jnp.float32) for t in inp]
        z = jnp.tanh(zx_t + rec(rz, h))
        i_t = ix_t + rec(ri, h)
        f_t = fx_t + rec(rf, h)
        o = jax.nn.sigmoid(ox_t + rec(ro, h))
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(logf + m - m_new)
        c_new = f_p * c + i_p * z
        n_new = f_p * n + i_p
        h_new = o * (c_new / jnp.maximum(n_new, 1e-6))
        return (c_new, n_new, h_new, m_new), h_new

    xs = (zx.swapaxes(0, 1), ix.swapaxes(0, 1), fx.swapaxes(0, 1),
          ox.swapaxes(0, 1))
    state, hs = jax.lax.scan(step, state, xs)
    return hs.swapaxes(0, 1).astype(x_raw.dtype), state


def _slstm_ffn(p, cfg, h):
    from repro.models.common import apply_norm
    hn = apply_norm({"scale": p["ffn_norm_scale"]}, h, "rmsnorm")
    f = jax.nn.gelu(jnp.einsum("bsd,df->bsf", hn, p["ffn_wi"].astype(h.dtype)))
    f = f * jnp.einsum("bsd,df->bsf", hn, p["ffn_wg"].astype(h.dtype))
    return h + jnp.einsum("bsf,fd->bsd", f, p["ffn_wo"].astype(h.dtype))


def slstm_block_forward(p, cfg, x):
    xc = jax.nn.silu(conv1d_causal(p["conv"], x))
    h, _ = slstm_scan(p, cfg, xc, x)
    return _slstm_ffn(p, cfg, h)


def slstm_block_prefill(p, cfg, x):
    xc = jax.nn.silu(conv1d_causal(p["conv"], x))
    h, state = slstm_scan(p, cfg, xc, x)
    out = _slstm_ffn(p, cfg, h)
    cw = cfg.conv_width
    conv_state = x[:, -(cw - 1):] if cw > 1 else x[:, :0]
    return out, {"c": state[0], "n": state[1], "h": state[2], "m": state[3],
                 "conv": conv_state}


def slstm_block_decode(p, cfg, x_t, st):
    xt = x_t[:, 0]
    xc_t, conv_state = conv1d_decode(p["conv"], xt, st["conv"])
    xc_t = jax.nn.silu(xc_t)
    h, state = slstm_scan(p, cfg, xc_t[:, None], xt[:, None],
                          (st["c"], st["n"], st["h"], st["m"]))
    out = _slstm_ffn(p, cfg, h)
    return out, {"c": state[0], "n": state[1], "h": state[2], "m": state[3],
                 "conv": conv_state}
