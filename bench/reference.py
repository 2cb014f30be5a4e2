"""Plain references, and the weights and operands the benchmark makes.

Nothing here imports the program. The references follow the published
description in float32 at `Precision.HIGHEST` (on a TPU a float32 matmul is
otherwise done in bfloat16 passes). Each takes a `low` flag: the same
computation with every matmul operand rounded to float8 (e4m3, scaled per
tensor), the precision step below the bfloat16 the programs compute in. It
is the control the comparisons must reject; the runs never compute it.

The weights of a served model are made here from the seed, in one jitted
call on the device, and made again the same way for the reference after the
program's state is freed: the reference takes nothing the program made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0     # largest finite float8_e4m3fn


def q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, low: bool = False):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if low:
        x, w = q8(x), q8(w)
    return jnp.matmul(x, w, precision=HI)


# ---------------------------------------------------------------- kernels

def _blockwise(err_and_top, n: int, rows: int, *arrays) -> float:
    """max |error| / max |reference| over blocks of `rows` leading rows."""
    worst, top = 0.0, 0.0
    for r in range(0, n, rows):
        e, t = err_and_top(*(x[r:r + rows] if x is not None else None
                             for x in arrays))
        worst, top = max(worst, float(e)), max(top, float(t))
    return worst / max(top, 1e-30) if np.isfinite(worst) else float("inf")


@functools.partial(jax.jit, static_argnames="low")
def _matmul_gap(a, b, o, low):
    want = mm(a, b)
    got = mm(a, b, True) if low else o.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def matmul_err(out, a, b, low: bool = False, rows: int = 2048) -> float:
    """rel_err of `out` against A @ B, in blocks of rows. With `low` the
    float8 control takes the place of `out` (which may be None)."""
    return _blockwise(lambda a_, o: _matmul_gap(a_, b, o, low),
                      a.shape[0], rows, a, out)


def attention(q, k, v, window: int = 0, low: bool = False):
    """Causal (windowed) softmax attention of one head per row of the
    batch: q, k, v [B, S, D] -> [B, S, D] float32."""
    S, D = q.shape[1], q.shape[2]
    f32 = lambda x: q8(x) if low else x.astype(jnp.float32)  # noqa: E731
    s = jnp.einsum("bqd,bkd->bqk", f32(q), f32(k), precision=HI)
    s = s / math.sqrt(D)
    qp, kp = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = kp <= qp
    if window > 0:
        mask = mask & (kp > qp - window)
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", f32(p), f32(v), precision=HI)


@functools.partial(jax.jit, static_argnames=("window", "low"))
def _attention_gap(q, k, v, o, window, low):
    want = attention(q, k, v, window)
    got = attention(q, k, v, window, True) if low else o.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want))


def attention_err(out, q, k, v, window: int, low: bool = False,
                  rows: int = 8) -> float:
    """rel_err of `out` against causal attention, in blocks of heads."""
    return _blockwise(lambda q_, k_, v_, o: _attention_gap(
        q_, k_, v_, o, window=window, low=low), q.shape[0], rows, q, k, v,
        out)


# ---------------------------------------------------------------- weights

def weight_shapes(s: dict) -> Dict[str, tuple]:
    L, d, H, G, hd, ff, V = (s["layers"], s["d_model"], s["heads"],
                             s["kv_heads"], s["head_dim"], s["d_ff"],
                             s["vocab"])
    shapes = {"embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
              "ln_attn": (L, d), "wq": (L, d, H, hd), "wk": (L, d, G, hd),
              "wv": (L, d, G, hd), "wo": (L, H, hd, d), "ln_mlp": (L, d),
              "wi": (L, d, ff), "wo_mlp": (L, ff, d)}
    if s["glu"]:
        shapes["wg"] = (L, d, ff)
    return shapes


def make_weights(key, s: dict) -> Dict[str, jax.Array]:
    """Random weights of a decoder from a key: matrices N(0, 1/fan_in),
    the embedding N(0, 1), norm scales 1 + N(0, 0.1^2)."""
    d, H, hd, ff = s["d_model"], s["heads"], s["head_dim"], s["d_ff"]
    fan_in = {"embed": 1, "lm_head": d, "wq": d, "wk": d, "wv": d,
              "wo": H * hd, "wi": d, "wg": d, "wo_mlp": ff}
    dtype = jnp.dtype(s["param_dtype"])
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(s).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in fan_in:
            w = z / math.sqrt(fan_in[name])
        else:
            w = 1.0 + 0.1 * z
        out[name] = w.astype(dtype)
    return out


# ---------------------------------------------------------- model forward

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotary embedding, rotate-half form: x [T, heads, D], pos [T]."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decoder_layer(x, w: Dict[str, jax.Array], s: dict, low: bool,
                  block: int = 512):
    """One pre-norm decoder layer over a whole sequence x [T, d]: GQA
    attention with RoPE, causal within a sliding window, then a SiLU-gated
    MLP; each with a residual."""
    T, d = x.shape
    H, G, hd, eps = s["heads"], s["kv_heads"], s["head_dim"], s["norm_eps"]
    R, win = H // G, s["window"]
    pos = jnp.arange(T)
    h = rmsnorm(x, w["ln_attn"], eps)
    q = mm(h, w["wq"].reshape(d, H * hd), low).reshape(T, H, hd)
    k = mm(h, w["wk"].reshape(d, G * hd), low).reshape(T, G, hd)
    v = mm(h, w["wv"].reshape(d, G * hd), low).reshape(T, G, hd)
    q, k = rope(q, pos, s["rope_theta"]), rope(k, pos, s["rope_theta"])
    k = jnp.repeat(k, R, axis=1)        # query head h reads KV head h // R
    v = jnp.repeat(v, R, axis=1)
    f = q8 if low else (lambda t: t)
    outs = []
    for b0 in range(0, T, block):       # query blocks keep the logits small
        qb = q[b0:b0 + block]
        sc = jnp.einsum("qhd,khd->hqk", f(qb), f(k), precision=HI)
        sc = sc / math.sqrt(hd)
        qp = pos[b0:b0 + block][:, None]
        mask = pos[None, :] <= qp
        if win > 0:
            mask = mask & (pos[None, :] > qp - win)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", f(p), f(v), precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, H * hd)
    x = x + mm(o, w["wo"].reshape(H * hd, d), low)
    h = rmsnorm(x, w["ln_mlp"], eps)
    up = jax.nn.silu(mm(h, w["wi"], low))
    if "wg" in w:
        up = up * mm(h, w["wg"], low)
    return x + mm(up, w["wo_mlp"], low)


_LAYER = ("ln_attn", "wq", "wk", "wv", "wo", "ln_mlp", "wi", "wg", "wo_mlp")
BUCKET = 512     # sequences are padded at the end to a multiple of this


@functools.lru_cache(maxsize=None)
def _jitted(sizes: tuple, low: bool):
    s = dict(sizes)
    layer = jax.jit(lambda x, lw: decoder_layer(x, lw, s, low))
    head = jax.jit(lambda x, fn, lm: mm(rmsnorm(x, fn, s["norm_eps"]), lm,
                                        low))
    return layer, head


def decoder_logits(w: Dict[str, jax.Array], s: dict,
                   tokens: Sequence[int], at: Sequence[int],
                   low: bool = False) -> jax.Array:
    """Logits [len(at), vocab] at positions `at` of the sequence `tokens`,
    running the layers one at a time. The sequence is padded at its end to
    a multiple of BUCKET, which the causal mask hides from every earlier
    position, so that few shapes compile."""
    T = len(tokens)
    tok = np.zeros(-(-T // BUCKET) * BUCKET, np.int32)
    tok[:T] = tokens
    layer, head = _jitted(tuple(sorted(s.items())), low)
    x = w["embed"].astype(jnp.float32)[jnp.asarray(tok)]
    for li in range(s["layers"]):
        x = layer(x, {n: w[n][li].astype(jnp.float32)
                      for n in _LAYER if n in w})
    return head(x[jnp.asarray(np.asarray(at, np.int32))],
                w["final_norm"].astype(jnp.float32), w["lm_head"])


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
    gap = jnp.max(ref, -1) - got
    return np.asarray(jnp.where(ok, gap, jnp.inf))


def free(*trees) -> None:
    """Delete the device buffers of the given trees now."""
    for t in trees:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jax.Array):
                leaf.delete()
