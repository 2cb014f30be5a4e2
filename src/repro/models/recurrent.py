"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Block: x -> [branch1: linear+GeLU] and [branch2: linear -> causal depthwise
conv(width 4) -> RG-LRU]; merge = branch1 * lru_out -> out projection.

RG-LRU:
  r_t = sigmoid(W_a y_t + b_a)          (recurrence gate)
  i_t = sigmoid(W_x y_t + b_x)          (input gate)
  log a_t = -c * softplus(Lambda) * r_t  (c = 8)
  h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * y_t)

Train/prefill uses jax.lax.associative_scan (parallel prefix) — the
TPU-friendly formulation; decode is a single-step update.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ParamBuilder

LRU_C = 8.0


def init_conv1d(b: ParamBuilder, name: str, width: int, channels: int):
    c = b.child(name)
    c.param("w", (width, channels), ("conv", "mlp"), scale=1.0 / width,
            cast=True)
    c.param("bias", (channels,), ("mlp",), init="zeros", cast=True)


def conv1d_causal(p, x: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: [B, S, C]."""
    width, C = p["w"].shape
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    kernel = p["w"].astype(x.dtype)[:, None, :]  # [W, 1, C] (WIO, depthwise)
    y = jax.lax.conv_general_dilated(
        xp, kernel, window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C)
    return y + p["bias"].astype(x.dtype)


def conv1d_decode(p, x_t: jax.Array, conv_state: jax.Array):
    """x_t: [B, C]; conv_state: [B, width-1, C] (oldest first)."""
    w = p["w"].astype(x_t.dtype)
    width = w.shape[0]
    full = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # [B, W, C]
    y = jnp.einsum("bwc,wc->bc", full, w) + p["bias"].astype(x_t.dtype)
    return y, full[:, 1:]


def init_rg_lru(b: ParamBuilder, width: int):
    c = b.child("lru")
    c.param("w_a", (width, width), ("mlp", "mlp2"), scale=1.0 / width ** 0.5,
            cast=True)
    c.param("b_a", (width,), ("mlp",), init="zeros", cast=True)
    c.param("w_x", (width, width), ("mlp", "mlp2"), scale=1.0 / width ** 0.5,
            cast=True)
    c.param("b_x", (width,), ("mlp",), init="zeros", cast=True)
    # Lambda init so that a ~ [0.9, 0.999] at r=1 (standard Griffin init range)
    c.param("lambda_raw", (width,), ("mlp",), init="ones", dtype=jnp.float32)


def _gates(p, y):
    r = jax.nn.sigmoid(
        jnp.einsum("...c,cd->...d", y, p["w_a"].astype(y.dtype))
        + p["b_a"].astype(y.dtype))
    i = jax.nn.sigmoid(
        jnp.einsum("...c,cd->...d", y, p["w_x"].astype(y.dtype))
        + p["b_x"].astype(y.dtype))
    log_a = (-LRU_C * jax.nn.softplus(p["lambda_raw"]) *
             r.astype(jnp.float32))
    return log_a, i


def rg_lru_forward(p, y: jax.Array, h0=None) -> jax.Array:
    """y: [B, S, C] -> [B, S, C] via parallel associative scan."""
    log_a, i = _gates(p, y)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 0.0, 1.0)) * (
        i.astype(jnp.float32) * y.astype(jnp.float32))

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    a_c, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    if h0 is not None:
        h = h + a_c * h0[:, None, :].astype(jnp.float32)
    return h.astype(y.dtype)


def rg_lru_step(p, y_t: jax.Array, h_prev: jax.Array):
    """y_t: [B, C], h_prev: [B, C] (fp32)."""
    log_a, i = _gates(p, y_t)
    a = jnp.exp(log_a)
    h = a * h_prev + jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 0.0, 1.0)) * (
        i.astype(jnp.float32) * y_t.astype(jnp.float32))
    return h.astype(y_t.dtype), h


def init_recurrent_block(b: ParamBuilder, cfg):
    d = cfg.d_model
    w = cfg.lru_width or d
    b.param("w_branch1", (d, w), ("embed", "mlp"), cast=True)
    b.param("w_branch2", (d, w), ("embed", "mlp"), cast=True)
    init_conv1d(b, "conv", cfg.conv_width, w)
    init_rg_lru(b, w)
    b.param("w_out", (w, d), ("mlp", "embed"), cast=True)


def recurrent_block_forward(p, cfg, x: jax.Array) -> jax.Array:
    from repro.distributed.act_sharding import constrain
    b1 = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_branch1"].astype(x.dtype)))
    u = jnp.einsum("bsd,dw->bsw", x, p["w_branch2"].astype(x.dtype))
    b1 = constrain(b1, "dp", None, "tp")
    u = constrain(u, "dp", None, "tp")
    u = conv1d_causal(p["conv"], u)
    lru_out = rg_lru_forward(p["lru"], u)
    return jnp.einsum("bsw,wd->bsd", b1 * lru_out, p["w_out"].astype(x.dtype))


def recurrent_block_prefill(p, cfg, x: jax.Array):
    """Returns (y, state) where state = {'h': [B,W] fp32, 'conv': [B,cw-1,W]}."""
    b1 = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_branch1"].astype(x.dtype)))
    u = jnp.einsum("bsd,dw->bsw", x, p["w_branch2"].astype(x.dtype))
    uc = conv1d_causal(p["conv"], u)
    log_a, i = _gates(p["lru"], uc)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 0.0, 1.0)) * (
        i.astype(jnp.float32) * uc.astype(jnp.float32))

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    _, h_all = jax.lax.associative_scan(combine, (a, gated), axis=1)
    lru_out = h_all.astype(x.dtype)
    y = jnp.einsum("bsw,wd->bsd", b1 * lru_out, p["w_out"].astype(x.dtype))
    cw = cfg.conv_width
    state = {
        "h": h_all[:, -1],                     # [B, W] fp32
        "conv": u[:, -(cw - 1):].astype(x.dtype) if cw > 1 else
                jnp.zeros((x.shape[0], 0, u.shape[-1]), x.dtype),
    }
    return y, state


def recurrent_block_decode(p, cfg, x_t: jax.Array, state):
    """x_t: [B, 1, d] -> (y [B,1,d], new_state)."""
    xt = x_t[:, 0]
    b1 = jax.nn.gelu(jnp.einsum("bd,dw->bw", xt, p["w_branch1"].astype(xt.dtype)))
    u = jnp.einsum("bd,dw->bw", xt, p["w_branch2"].astype(xt.dtype))
    uc, conv_state = conv1d_decode(p["conv"], u, state["conv"])
    lru_out, h = rg_lru_step(p["lru"], uc, state["h"])
    y = jnp.einsum("bw,wd->bd", b1 * lru_out, p["w_out"].astype(xt.dtype))
    return y[:, None], {"h": h, "conv": conv_state}
