"""Block composition + scanned heterogeneous stacks.

A stack is factored as (prefix, repeated group, suffix):
  dense:           ([], (attention,), L, [])
  deepseek-v3:     ([attention]*3, (moe_attention,), 58, [])
  dbrx:            ([], (moe_attention,), 40, [])
  recurrentgemma:  ([], (recurrent, recurrent, attention), 8, [recurrent]*2)
  xlstm:           ([], (mlstm, slstm), 12, [])
  vision-90b:      ([], (attention x4, cross_attention), 20, [])
  whisper decoder: ([], (encdec_attention,), 4, [])

The repeated group is scanned with jax.lax.scan over stacked params so HLO
size / compile time is depth-independent; remat policy applies to the scanned
body.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (ParamBuilder, apply_mlp, apply_norm,
                                 init_mlp, init_norm, stack_params)

PyTree = Any


# ---------------------------------------------------------------------------
# Stack plan
# ---------------------------------------------------------------------------


def layer_kinds(cfg) -> List[str]:
    if cfg.is_encoder_decoder:
        return ["encdec_attention"] * cfg.num_layers
    if cfg.block_pattern:
        pat = cfg.block_pattern
        return [pat[i % len(pat)] for i in range(cfg.num_layers)]
    if cfg.cross_attn_every > 0:
        kinds = []
        i = 0
        while len(kinds) < cfg.num_layers:
            for _ in range(cfg.cross_attn_every):
                if len(kinds) < cfg.num_layers:
                    kinds.append("attention")
            if len(kinds) < cfg.num_layers:
                kinds.append("cross_attention")
        return kinds
    if cfg.moe is not None:
        nd = cfg.moe.first_dense_layers
        return ["attention"] * nd + ["moe_attention"] * (cfg.num_layers - nd)
    return ["attention"] * cfg.num_layers


def stack_plan(cfg) -> Tuple[List[str], Tuple[str, ...], int, List[str]]:
    """Returns (prefix_kinds, group_kinds, n_groups, suffix_kinds)."""
    kinds = layer_kinds(cfg)
    if not cfg.scan_layers:
        return kinds, (), 0, []
    # choose the repeating unit
    if cfg.is_encoder_decoder:
        unit: Tuple[str, ...] = ("encdec_attention",)
    elif cfg.block_pattern:
        unit = tuple(cfg.block_pattern)
    elif cfg.cross_attn_every > 0:
        unit = tuple(["attention"] * cfg.cross_attn_every + ["cross_attention"])
    elif cfg.moe is not None:
        unit = ("moe_attention",)
    else:
        unit = ("attention",)
    # strip non-matching prefix (e.g. dsv3 leading dense layers)
    prefix: List[str] = []
    i = 0
    while i < len(kinds) and kinds[i] != unit[0]:
        prefix.append(kinds[i])
        i += 1
    rest = kinds[i:]
    n_groups = 0
    j = 0
    while j + len(unit) <= len(rest) and tuple(rest[j: j + len(unit)]) == unit:
        n_groups += 1
        j += len(unit)
    suffix = rest[j:]
    if n_groups == 0:
        return kinds, (), 0, []
    return prefix, unit, n_groups, suffix


# ---------------------------------------------------------------------------
# Single block init / forward / prefill / decode
# ---------------------------------------------------------------------------


def init_block(b: ParamBuilder, cfg, kind: str):
    if kind == "attention" or kind == "moe_attention":
        init_norm(b, "ln_attn", cfg.d_model, cfg.norm)
        a = b.child("attn")
        if cfg.mla is not None:
            attn.init_mla(a, cfg)
        else:
            attn.init_attention(a, cfg)
        init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
        if kind == "moe_attention":
            moe_mod.init_moe(b, cfg)
        else:
            init_mlp(b, cfg.d_model, cfg.d_ff, cfg.use_glu)
    elif kind == "cross_attention":
        init_norm(b, "ln_attn", cfg.d_model, cfg.norm)
        a = b.child("attn")
        attn.init_attention(a, cfg, cross=True)
        init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.use_glu)
        b.param("gate_mlp", (1,), (None,), init="zeros", dtype=jnp.float32)
    elif kind == "encdec_attention":
        init_norm(b, "ln_self", cfg.d_model, cfg.norm)
        attn.init_attention(b.child("self_attn"), cfg)
        init_norm(b, "ln_cross", cfg.d_model, cfg.norm)
        attn.init_attention(b.child("cross_attn"), cfg, cross=True)
        init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.use_glu)
    elif kind == "encoder_attention":
        init_norm(b, "ln_attn", cfg.d_model, cfg.norm)
        attn.init_attention(b.child("attn"), cfg)
        init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.use_glu)
    elif kind == "recurrent":
        init_norm(b, "ln_rec", cfg.d_model, cfg.norm)
        rec_mod.init_recurrent_block(b.child("rec"), cfg)
        init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
        init_mlp(b, cfg.d_model, cfg.d_ff, cfg.use_glu)
    elif kind == "mlstm":
        init_norm(b, "ln", cfg.d_model, cfg.norm)
        xlstm_mod.init_mlstm_block(b.child("cell"), cfg)
    elif kind == "slstm":
        init_norm(b, "ln", cfg.d_model, cfg.norm)
        xlstm_mod.init_slstm_block(b.child("cell"), cfg)
    else:
        raise ValueError(kind)


def block_forward(p, cfg, kind: str, x, positions, extras) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attention", "moe_attention"):
        h = apply_norm(p["ln_attn"], x, cfg.norm)
        if cfg.mla is not None:
            y = attn.mla_forward(p["attn"], cfg, h, positions)
        else:
            y = attn.attention_forward(p["attn"], cfg, h, positions)
        x = x + y
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        if kind == "moe_attention":
            y, aux = moe_mod.moe_forward(p["moe"], cfg, h,
                                         extras.get("moe_impl", "scatter"))
        else:
            y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        x = x + y
    elif kind == "cross_attention":
        h = apply_norm(p["ln_attn"], x, cfg.norm)
        y = attn.attention_forward(p["attn"], cfg, h, positions, kind="full",
                                   kv_src=extras["kv_src"])
        x = x + y
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        x = x + y * jnp.tanh(p["gate_mlp"]).astype(x.dtype)
    elif kind == "encdec_attention":
        h = apply_norm(p["ln_self"], x, cfg.norm)
        x = x + attn.attention_forward(p["self_attn"], cfg, h, positions,
                                       kind="causal")
        h = apply_norm(p["ln_cross"], x, cfg.norm)
        x = x + attn.attention_forward(p["cross_attn"], cfg, h, positions,
                                       kind="full", kv_src=extras["kv_src"])
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        x = x + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
    elif kind == "encoder_attention":
        h = apply_norm(p["ln_attn"], x, cfg.norm)
        x = x + attn.attention_forward(p["attn"], cfg, h, positions, kind="full")
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        x = x + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
    elif kind == "recurrent":
        h = apply_norm(p["ln_rec"], x, cfg.norm)
        x = x + rec_mod.recurrent_block_forward(p["rec"], cfg, h)
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        x = x + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
    elif kind == "mlstm":
        h = apply_norm(p["ln"], x, cfg.norm)
        x = x + xlstm_mod.mlstm_block_forward(
            p["cell"], cfg, h, extras.get("chunk", cfg.scan_chunk))
    elif kind == "slstm":
        h = apply_norm(p["ln"], x, cfg.norm)
        x = x + xlstm_mod.slstm_block_forward(p["cell"], cfg, h)
    else:
        raise ValueError(kind)
    return x, aux


def block_prefill(p, cfg, kind: str, x, positions, cache_len: int, extras):
    """Returns (x, cache)."""
    if kind in ("attention", "moe_attention"):
        h = apply_norm(p["ln_attn"], x, cfg.norm)
        if cfg.mla is not None:
            y, cache = attn.mla_prefill(p["attn"], cfg, h, positions, cache_len)
        else:
            y, cache = attn.attention_prefill(p["attn"], cfg, h, positions,
                                              cache_len)
        x = x + y
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        if kind == "moe_attention":
            y, _ = moe_mod.moe_forward(p["moe"], cfg, h,
                                       extras.get("moe_impl", "scatter"))
        else:
            y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x + y, cache
    if kind == "cross_attention":
        cache = attn.cross_attention_build_cache(p["attn"], cfg, extras["kv_src"])
        h = apply_norm(p["ln_attn"], x, cfg.norm)
        y = attn.attention_forward(p["attn"], cfg, h, positions, kind="full",
                                   kv_src=extras["kv_src"])
        x = x + y
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x + y * jnp.tanh(p["gate_mlp"]).astype(x.dtype), cache
    if kind == "encdec_attention":
        h = apply_norm(p["ln_self"], x, cfg.norm)
        y, self_cache = attn.attention_prefill(p["self_attn"], cfg, h,
                                               positions, cache_len,
                                               kind="causal")
        x = x + y
        cross_cache = attn.cross_attention_build_cache(
            p["cross_attn"], cfg, extras["kv_src"])
        h = apply_norm(p["ln_cross"], x, cfg.norm)
        x = x + attn.attention_forward(p["cross_attn"], cfg, h, positions,
                                       kind="full", kv_src=extras["kv_src"])
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        x = x + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x, {"self": self_cache, "cross": cross_cache}
    if kind == "recurrent":
        h = apply_norm(p["ln_rec"], x, cfg.norm)
        y, state = rec_mod.recurrent_block_prefill(p["rec"], cfg, h)
        x = x + y
        h = apply_norm(p["ln_mlp"], x, cfg.norm)
        return x + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu), state
    if kind == "mlstm":
        h = apply_norm(p["ln"], x, cfg.norm)
        y, state = xlstm_mod.mlstm_block_prefill(
            p["cell"], cfg, h, extras.get("chunk", cfg.scan_chunk))
        return x + y, state
    if kind == "slstm":
        h = apply_norm(p["ln"], x, cfg.norm)
        y, state = xlstm_mod.slstm_block_prefill(p["cell"], cfg, h)
        return x + y, state
    raise ValueError(kind)


def block_decode(p, cfg, kind: str, x_t, cache, cur_pos, extras):
    """x_t: [B, 1, d]. Returns (x_t, new_cache)."""
    attend_fn = extras.get("attend_fn")
    if kind in ("attention", "moe_attention"):
        h = apply_norm(p["ln_attn"], x_t, cfg.norm)
        if cfg.mla is not None:
            y, cache = attn.mla_decode(p["attn"], cfg, h, cache, cur_pos)
        else:
            y, cache = attn.attention_decode(p["attn"], cfg, h, cache, cur_pos,
                                             attend_fn=attend_fn)
        x_t = x_t + y
        h = apply_norm(p["ln_mlp"], x_t, cfg.norm)
        if kind == "moe_attention":
            y, _ = moe_mod.moe_forward(p["moe"], cfg, h,
                                       extras.get("moe_impl", "scatter"))
        else:
            y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x_t + y, cache
    if kind == "cross_attention":
        h = apply_norm(p["ln_attn"], x_t, cfg.norm)
        y = attn.cross_attention_decode(p["attn"], cfg, h, cache)
        x_t = x_t + y
        h = apply_norm(p["ln_mlp"], x_t, cfg.norm)
        y = apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x_t + y * jnp.tanh(p["gate_mlp"]).astype(x_t.dtype), cache
    if kind == "encdec_attention":
        h = apply_norm(p["ln_self"], x_t, cfg.norm)
        y, self_cache = attn.attention_decode(p["self_attn"], cfg, h,
                                              cache["self"], cur_pos,
                                              attend_fn=attend_fn)
        x_t = x_t + y
        h = apply_norm(p["ln_cross"], x_t, cfg.norm)
        x_t = x_t + attn.cross_attention_decode(p["cross_attn"], cfg, h,
                                                cache["cross"])
        h = apply_norm(p["ln_mlp"], x_t, cfg.norm)
        x_t = x_t + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu)
        return x_t, {"self": self_cache, "cross": cache["cross"]}
    if kind == "recurrent":
        h = apply_norm(p["ln_rec"], x_t, cfg.norm)
        y, state = rec_mod.recurrent_block_decode(p["rec"], cfg, h, cache)
        x_t = x_t + y
        h = apply_norm(p["ln_mlp"], x_t, cfg.norm)
        return x_t + apply_mlp(p["mlp"], h, cfg.act, cfg.use_glu), state
    if kind == "mlstm":
        h = apply_norm(p["ln"], x_t, cfg.norm)
        y, state = xlstm_mod.mlstm_block_decode(p["cell"], cfg, h, cache)
        return x_t + y, state
    if kind == "slstm":
        h = apply_norm(p["ln"], x_t, cfg.norm)
        y, state = xlstm_mod.slstm_block_decode(p["cell"], cfg, h, cache)
        return x_t + y, state
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Stack init / forward / prefill / decode (scan over repeated groups)
# ---------------------------------------------------------------------------


def init_stack(b: ParamBuilder, cfg, kinds_override: Optional[List[str]] = None):
    """Initializes {'prefix': [...], 'groups': stacked, 'suffix': [...]}."""
    if kinds_override is not None:
        prefix, unit, n_groups, suffix = kinds_override, (), 0, []
    else:
        prefix, unit, n_groups, suffix = stack_plan(cfg)
    s = b.child("stack")
    pfx = s.child("prefix")
    for i, kind in enumerate(prefix):
        init_block(pfx.child(f"l{i}"), cfg, kind)
    if n_groups:
        built = []

        def build_group(key):
            gb = ParamBuilder(key, "float32", abstract=b.abstract)
            gb.dtype = s.dtype
            for pos, kind in enumerate(unit):
                init_block(gb.child(f"b{pos}"), cfg, kind)
            built.append(gb)
            return gb.params

        if b.abstract:
            s.params["groups"] = stack_params([build_group(None)] * n_groups)
        else:
            # sample every leaf at its stacked shape, vmapped over the group
            # keys (the same values as one group at a time): a list of groups
            # plus their stack would hold the weights twice, which a 1.8 B
            # f32 model on one 16 GB chip cannot afford
            keys = jnp.stack([s.next_key() for _ in range(n_groups)])
            s.params["groups"] = jax.vmap(build_group)(keys)
        axes_tree = built[0].axes
        from repro.models.common import map_axes
        s.axes["groups"] = map_axes(lambda a: ("layers",) + tuple(a), axes_tree)
        s.cast["groups"] = built[0].cast
    sfx = s.child("suffix")
    for i, kind in enumerate(suffix):
        init_block(sfx.child(f"l{i}"), cfg, kind)


@functools.lru_cache(maxsize=64)
def stack_axes(cfg) -> Dict[str, Any]:
    """Logical-axes trees for the stack's prefix / group-slice / suffix params
    (group axes have the leading 'layers' dim stripped). Used by the ZeRO-3
    just-in-time weight-gather constraints (distributed.act_sharding)."""
    b = ParamBuilder(None, cfg.param_dtype, abstract=True)
    init_stack(b, cfg)
    axes = b.axes["stack"]
    out = {"prefix": axes.get("prefix", {}), "suffix": axes.get("suffix", {})}
    if "groups" in axes:
        from repro.models.common import map_axes
        out["groups"] = map_axes(lambda a: tuple(a[1:]), axes["groups"])
    return out


def _maybe_gather(p_blk, axes_blk):
    from repro.distributed import act_sharding
    if act_sharding.current() is None:
        return p_blk
    return act_sharding.gather_params(p_blk, axes_blk)


def _remat(fn, cfg):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def stack_forward(params, cfg, x, positions, extras,
                  kinds_override: Optional[List[str]] = None):
    if kinds_override is not None:
        prefix, unit, n_groups, suffix = kinds_override, (), 0, []
    else:
        prefix, unit, n_groups, suffix = stack_plan(cfg)
    sp = params["stack"]
    aux = jnp.zeros((), jnp.float32)

    saxes = stack_axes(cfg) if kinds_override is None else None

    def one_block(p_blk, kind, x, aux, axes_blk=None):
        def f(p_blk, x, aux):
            if axes_blk is not None:
                p_blk = _maybe_gather(p_blk, axes_blk)
            x, a = block_forward(p_blk, cfg, kind, x, positions, extras)
            return x, aux + a
        return _remat(f, cfg)(p_blk, x, aux)

    for i, kind in enumerate(prefix):
        x, aux = one_block(sp["prefix"][f"l{i}"], kind, x, aux,
                           saxes["prefix"].get(f"l{i}") if saxes else None)
    if n_groups:
        def body(carry, gp):
            x, aux = carry
            if saxes is not None:
                gp = _maybe_gather(gp, saxes["groups"])
            for pos, kind in enumerate(unit):
                x, a = block_forward(gp[f"b{pos}"], cfg, kind, x, positions,
                                     extras)
                aux = aux + a
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(_remat(body, cfg), (x, aux), sp["groups"])
    for i, kind in enumerate(suffix):
        x, aux = one_block(sp["suffix"][f"l{i}"], kind, x, aux,
                           saxes["suffix"].get(f"l{i}") if saxes else None)
    return x, aux


def stack_prefill(params, cfg, x, positions, cache_len, extras,
                  kinds_override: Optional[List[str]] = None):
    if kinds_override is not None:
        prefix, unit, n_groups, suffix = kinds_override, (), 0, []
    else:
        prefix, unit, n_groups, suffix = stack_plan(cfg)
    sp = params["stack"]
    caches: Dict[str, Any] = {"prefix": {}, "suffix": {}}
    for i, kind in enumerate(prefix):
        x, c = block_prefill(sp["prefix"][f"l{i}"], cfg, kind, x, positions,
                             cache_len, extras)
        caches["prefix"][f"l{i}"] = c
    if n_groups:
        saxes = stack_axes(cfg) if kinds_override is None else None

        def body(x, gp):
            if saxes is not None:
                gp = _maybe_gather(gp, saxes["groups"])
            gcaches = {}
            for pos, kind in enumerate(unit):
                x, c = block_prefill(gp[f"b{pos}"], cfg, kind, x, positions,
                                     cache_len, extras)
                gcaches[f"b{pos}"] = c
            return x, gcaches

        x, gc = jax.lax.scan(body, x, sp["groups"])
        caches["groups"] = gc
    for i, kind in enumerate(suffix):
        x, c = block_prefill(sp["suffix"][f"l{i}"], cfg, kind, x, positions,
                             cache_len, extras)
        caches["suffix"][f"l{i}"] = c
    return x, caches


def stack_decode(params, cfg, x_t, caches, cur_pos, extras,
                 kinds_override: Optional[List[str]] = None):
    if kinds_override is not None:
        prefix, unit, n_groups, suffix = kinds_override, (), 0, []
    else:
        prefix, unit, n_groups, suffix = stack_plan(cfg)
    sp = params["stack"]
    new_caches: Dict[str, Any] = {"prefix": {}, "suffix": {}}
    for i, kind in enumerate(prefix):
        x_t, c = block_decode(sp["prefix"][f"l{i}"], cfg, kind, x_t,
                              caches["prefix"][f"l{i}"], cur_pos, extras)
        new_caches["prefix"][f"l{i}"] = c
    if n_groups:
        def body(x_t, xs):
            gp, gc = xs
            ngc = {}
            for pos, kind in enumerate(unit):
                x_t, c = block_decode(gp[f"b{pos}"], cfg, kind, x_t,
                                      gc[f"b{pos}"], cur_pos, extras)
                ngc[f"b{pos}"] = c
            return x_t, ngc

        x_t, gc = jax.lax.scan(body, x_t, (sp["groups"], caches["groups"]))
        new_caches["groups"] = gc
    for i, kind in enumerate(suffix):
        x_t, c = block_decode(sp["suffix"][f"l{i}"], cfg, kind, x_t,
                              caches["suffix"][f"l{i}"], cur_pos, extras)
        new_caches["suffix"][f"l{i}"] = c
    return x_t, new_caches
