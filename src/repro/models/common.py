"""Shared model-building utilities.

Every parameter is created through ParamBuilder, which records a parallel tree
of *logical axis names* used by repro.distributed.sharding to build
NamedShardings, and a parallel tree of *cast flags* used by
Model.serving_params. Pure JAX; no flax.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

PyTree = Any

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int32": jnp.int32,
    "int8": jnp.int8,
}


def dtype_of(name: str):
    return DTYPES[name]


# ---------------------------------------------------------------------------
# Parameter builder with logical-axis tracking
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Accumulates params and their logical axes into parallel nested dicts.

    abstract=True records jax.ShapeDtypeStruct leaves instead of sampling —
    used to build shardings for huge models without allocating anything.

    `cast` holds one flag per param: True where every read of the leaf in
    the forward, prefill and decode paths is `.astype(<activation dtype>)`,
    so serving may hold it in the activation dtype with the same results.
    """

    def __init__(self, key: Optional[jax.Array], param_dtype: str = "float32",
                 abstract: bool = False):
        self._key = key
        self.abstract = abstract
        self.dtype = dtype_of(param_dtype)
        self.params: dict = {}
        self.axes: dict = {}
        self.cast: dict = {}

    def next_key(self) -> Optional[jax.Array]:
        if self.abstract:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def child(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self.next_key(), "float32", abstract=self.abstract)
        sub.dtype = self.dtype
        self.params[name] = sub.params
        self.axes[name] = sub.axes
        self.cast[name] = sub.cast
        return sub

    def param(
        self,
        name: str,
        shape: Sequence[int],
        axes: Sequence[Optional[str]],
        init: str = "normal",
        scale: Optional[float] = None,
        dtype=None,
        cast: bool = False,
    ) -> jax.Array:
        assert len(shape) == len(axes), (name, shape, axes)
        dtype = dtype or self.dtype
        self.cast[name] = cast
        if self.abstract:
            leaf = jax.ShapeDtypeStruct(tuple(shape), dtype)
            self.params[name] = leaf
            self.axes[name] = tuple(axes)
            return leaf
        key = self.next_key()
        if init == "normal":
            if scale is None:  # fan-in scaling
                fan_in = shape[0] if len(shape) == 1 else int(
                    math.prod(shape[:-1]) if len(shape) == 2 else math.prod(shape) / shape[-1])
                fan_in = max(1, fan_in)
                scale = 1.0 / math.sqrt(fan_in)
            arr = jax.random.normal(key, tuple(shape), dtype=jnp.float32) * scale
        elif init == "zeros":
            arr = jnp.zeros(tuple(shape), dtype=jnp.float32)
        elif init == "ones":
            arr = jnp.ones(tuple(shape), dtype=jnp.float32)
        else:
            raise ValueError(init)
        arr = arr.astype(dtype)
        self.params[name] = arr
        self.axes[name] = tuple(axes)
        return arr


def stack_params(trees: Sequence[PyTree]) -> PyTree:
    """Stack a list of identically-structured param trees along a new axis 0.

    Handles both concrete arrays and abstract ShapeDtypeStruct leaves.
    """
    def stack(*xs):
        if isinstance(xs[0], jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((len(xs), *xs[0].shape), xs[0].dtype)
        return jnp.stack(xs, axis=0)

    return jax.tree.map(stack, *trees)


def is_axes_leaf(x) -> bool:
    """Leaves of an *axes tree* are tuples of axis names (str | None)."""
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def map_axes(fn: Callable, tree: PyTree) -> PyTree:
    """tree.map over an axes tree (tuples of names are leaves, not pytree nodes)."""
    return jax.tree.map(fn, tree, is_leaf=is_axes_leaf)


def stack_axes(axes_tree: PyTree) -> PyTree:
    """Prepend the 'layers' logical axis to every leaf of an axes tree."""
    return jax.tree.map(
        lambda a: ("layers",) + tuple(a),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Norms / activations / embeddings
# ---------------------------------------------------------------------------


def init_norm(b: ParamBuilder, name: str, dim: int, kind: str):
    c = b.child(name)
    c.param("scale", (dim,), ("embed",), init="ones", dtype=jnp.float32)
    if kind == "layernorm":
        c.param("bias", (dim,), ("embed",), init="zeros", dtype=jnp.float32)


def apply_norm(p: PyTree, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * p["scale"]
    elif kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        raise ValueError(kind)
    return y.astype(orig_dtype)


def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    raise ValueError(name)


def sinusoidal_positions(seq_len: int, dim: int, dtype=jnp.float32) -> jax.Array:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    half = dim // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies [D/2]; with `scaling` (a RopeScaling), YaRN's:
    dims past the correction range are divided by the factor, dims before it
    kept, with a linear ramp between (DeepseekV3YarnRotaryEmbedding)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is not None:
        lo, hi = yarn_correction_range(scaling, head_dim, theta)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                        / max(hi - lo, 1e-3), 0.0, 1.0)
        freqs = freqs / scaling.factor * ramp + freqs * (1.0 - ramp)
    return freqs


def yarn_correction_range(scaling, dim: int, theta: float):
    """YaRN's (low, high) dims: where `beta_fast` and `beta_slow` rotations
    fit in the original context."""
    def dim_of(rotations):
        return dim * math.log(scaling.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    lo = math.floor(dim_of(scaling.beta_fast))
    hi = math.ceil(dim_of(scaling.beta_slow))
    return max(lo, 0), min(hi, dim - 1)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., S, H, D] (or [..., H, D] w/ scalar-per-row positions [..., S])."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)  # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]  # broadcast over head dim
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense helpers
# ---------------------------------------------------------------------------


def init_dense(b: ParamBuilder, name: str, in_dim: int, out_dim: int,
               in_axis: Optional[str], out_axis: Optional[str],
               init: str = "normal", scale: Optional[float] = None):
    b.param(name, (in_dim, out_dim), (in_axis, out_axis), init=init,
            scale=scale, cast=True)


def dense(w: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w.astype(x.dtype))


def init_mlp(b: ParamBuilder, d_model: int, d_ff: int, use_glu: bool,
             in_axis: str = "embed", hidden_axis: str = "mlp"):
    c = b.child("mlp")
    init_dense(c, "wi", d_model, d_ff, in_axis, hidden_axis)
    if use_glu:
        init_dense(c, "wg", d_model, d_ff, in_axis, hidden_axis)
    init_dense(c, "wo", d_ff, d_model, hidden_axis, in_axis)


def apply_mlp(p: PyTree, x: jax.Array, act_name: str, use_glu: bool) -> jax.Array:
    from repro.distributed.act_sharding import constrain
    act = activation(act_name)
    h = dense(p["wi"], x)
    h = constrain(h, *(("dp",) + (None,) * (h.ndim - 2) + ("tp",)))
    if use_glu:
        h = act(h) * dense(p["wg"], x)
    else:
        h = act(h)
    y = dense(p["wo"], h)
    return constrain(y, *(("dp",) + (None,) * (y.ndim - 1)))
