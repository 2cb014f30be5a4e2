"""Top-level language model: embeddings, stacks, loss, prefill/decode, specs.

build_model(cfg) returns a Model with pure functions:
    init(rng) -> params              (model.axes holds the logical-axes tree)
    serving_params(params) -> params (castable weights in the compute dtype)
    forward(params, batch) -> (logits, aux)
    loss(params, batch) -> (scalar, metrics)
    prefill(params, batch) -> (state, last_logits)
    decode_step(params, state, tokens[B]) -> (state, logits[B, V])

Batch keys: tokens/targets int32 [B,S]; enc-dec adds encoder_embeddings
[B, enc_len, d] (stub frontend); vlm adds frontend_embeddings [B, N_img, d].
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models.common import (ParamBuilder, apply_norm, dtype_of, init_norm,
                                 sinusoidal_positions)

PyTree = Any


def _sinusoid_at(positions: jax.Array, dim: int, dtype) -> jax.Array:
    """Sinusoidal embeddings at arbitrary positions [S] or [B,S] -> [...,S,dim]."""
    half = dim // 2
    freq = jnp.exp(-math.log(10000.0) *
                   jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    out = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)
    if out.ndim == 2:  # [S, dim] -> broadcastable over batch
        out = out[None]
    return out


def cache_length(cfg: ModelConfig, context_len: int) -> int:
    """KV-cache capacity for a decode shape with `context_len` of context."""
    if cfg.attention_kind == "sliding" and cfg.sliding_window > 0:
        return min(context_len, cfg.sliding_window)
    if cfg.attention_kind == "local" and cfg.local_window > 0:
        return min(context_len, cfg.local_window)
    return context_len


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    axes: PyTree = None

    # ------------------------------------------------------------------ init
    def init(self, rng: jax.Array) -> PyTree:
        params, _ = self.init_with_axes(rng)
        return params

    def init_with_axes(self, rng: jax.Array):
        b = self._build(rng, abstract=False)
        return b.params, b.axes

    def abstract_params_and_axes(self):
        """(ShapeDtypeStruct tree, axes tree) without allocating anything."""
        b = self._build(None, abstract=True)
        return b.params, b.axes

    def serving_params(self, params: PyTree) -> PyTree:
        """The tree the serving steps read: each leaf that every step reads
        only as `.astype(<activation dtype>)` (flagged where it is made) is
        cast to the activation dtype once, in one jitted call; every other
        leaf is the caller's own array. The cast is exact, so the steps
        compute the same values without casting weights on every call.
        Where the two dtypes agree this is `params` itself."""
        cfg = self.cfg
        if cfg.param_dtype == cfg.activation_dtype:
            return params
        leaves, treedef = jax.tree.flatten(params)
        flags = treedef.flatten_up_to(self._build(None, abstract=True).cast)
        cast = iter(_cast_to(dtype_of(cfg.activation_dtype),
                             [x for x, f in zip(leaves, flags) if f]))
        return jax.tree.unflatten(
            treedef, [next(cast) if f else x for x, f in zip(leaves, flags)])

    def _build(self, rng, abstract: bool) -> ParamBuilder:
        cfg = self.cfg
        b = ParamBuilder(rng, cfg.param_dtype, abstract=abstract)
        V = cfg.padded_vocab_size
        b.param("embed", (V, cfg.d_model), ("vocab", "embed"),
                scale=1.0, cast=True)
        if not cfg.tie_embeddings:
            b.param("lm_head", (cfg.d_model, V), ("embed", "vocab"),
                    scale=1.0 / math.sqrt(cfg.d_model), cast=True)
        init_norm(b, "final_norm", cfg.d_model, cfg.norm)
        tfm.init_stack(b, cfg)
        if cfg.is_encoder_decoder:
            enc = b.child("encoder")
            tfm.init_stack(enc, cfg,
                           kinds_override=["encoder_attention"] * cfg.encoder_layers)
            init_norm(b, "encoder_norm", cfg.d_model, cfg.norm)
        return b

    # ------------------------------------------------------------- internals
    def _embed(self, params, tokens, positions=None):
        """tokens [B,S]; positions [S] or [B,S] absolute positions."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(dtype_of(cfg.activation_dtype))
        if cfg.family == "hybrid":  # gemma-family embedding scaling
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if not cfg.use_rope and cfg.family != "ssm":
            # sinusoidal absolute positions (whisper); xLSTM uses none
            S = tokens.shape[1]
            if positions is None:
                positions = jnp.arange(S)
            x = x + _sinusoid_at(positions, cfg.d_model, x.dtype)
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg.norm)
        if cfg.tie_embeddings:
            w = params["embed"].astype(x.dtype)
            logits = jnp.einsum("...d,vd->...v", x, w)
        else:
            logits = jnp.einsum("...d,dv->...v", x,
                                params["lm_head"].astype(x.dtype))
        logits = logits.astype(dtype_of(cfg.logits_dtype))
        if cfg.padded_vocab_size != cfg.vocab_size:
            pad = cfg.padded_vocab_size - cfg.vocab_size
            neg = jnp.full((*logits.shape[:-1], pad), -1e30, logits.dtype)
            logits = jnp.concatenate([logits[..., : cfg.vocab_size], neg], -1)
        return logits

    def _encode(self, params, encoder_embeddings):
        cfg = self.cfg
        x = encoder_embeddings.astype(dtype_of(cfg.activation_dtype))
        S = x.shape[1]
        x = x + sinusoidal_positions(S, cfg.d_model, x.dtype)[None]
        positions = jnp.arange(S)
        x, _ = tfm.stack_forward(
            params["encoder"], cfg, x, positions, {},
            kinds_override=["encoder_attention"] * cfg.encoder_layers)
        return apply_norm(params["encoder_norm"], x, cfg.norm)

    def _extras(self, params, batch) -> Dict[str, Any]:
        cfg = self.cfg
        extras: Dict[str, Any] = dict(batch.get("extras", {}))
        if cfg.is_encoder_decoder:
            extras["kv_src"] = self._encode(params, batch["encoder_embeddings"])
        elif cfg.cross_attn_every > 0:
            extras["kv_src"] = batch["frontend_embeddings"].astype(
                dtype_of(cfg.activation_dtype))
        return extras

    # --------------------------------------------------------------- forward
    def forward(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        positions = jnp.arange(tokens.shape[1])
        extras = self._extras(params, batch)
        x, aux = tfm.stack_forward(params, cfg, x, positions, extras)
        return self._logits(params, x), aux

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        targets = batch["targets"]
        V = logits.shape[-1]
        logits32 = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits32, axis=-1)
        gold = jnp.take_along_axis(logits32, targets[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask", jnp.ones_like(targets, jnp.float32))
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = ((logz - gold) * mask).sum() / denom
        zloss = 1e-4 * ((logz ** 2) * mask).sum() / denom
        total = ce + zloss + aux
        return total, {"ce": ce, "zloss": zloss, "aux": aux,
                       "ppl_proxy": jnp.exp(jnp.clip(ce, max=20.0))}

    # --------------------------------------------------------------- serving
    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Processes batch['tokens'] [B,S]; returns (state, last_logits).

        max_len: total planned sequence length (context + decode steps); the
        KV cache is sized for it (default S + 64 headroom).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.arange(S)
        extras = self._extras(params, batch)
        clen = cache_length(cfg, max_len if max_len is not None else S + 64)
        x, caches = tfm.stack_prefill(params, cfg, x, positions, clen, extras)
        logits = self._logits(params, x[:, -1:])[:, 0]
        state = {"layers": caches,
                 "cur": jnp.full((B,), S, jnp.int32)}
        return state, logits

    @staticmethod
    def join_states(states):
        """Decode states of row groups prefilled apart, joined into one
        along the batch axis: the second axis of the scanned groups' caches
        (their first is the layer), the first of every other leaf."""
        def join(path, *xs):
            groups = any(getattr(k, "key", None) == "groups" for k in path)
            return jnp.concatenate(xs, axis=1 if groups else 0)
        return jax.tree_util.tree_map_with_path(join, *states)

    def decode_step(self, params, state, tokens):
        """tokens: [B] int32 -> (new_state, logits [B, V])."""
        cfg = self.cfg
        x = self._embed(params, tokens[:, None], positions=state["cur"][:, None])
        extras = dict(state.get("extras", {}))
        cur = state["cur"]
        x, caches = tfm.stack_decode(params, cfg, x, state["layers"], cur,
                                     extras)
        logits = self._logits(params, x)[:, 0]
        new_state = {k: v for k, v in state.items() if k != "extras"}
        new_state["layers"] = caches
        new_state["cur"] = cur + 1
        return new_state, logits

    # ------------------------------------------------------------- specs
    def init_decode_state_specs(self, batch_size: int, context_len: int):
        """ShapeDtypeStruct tree matching what prefill(context_len) returns."""
        cfg = self.cfg
        clen = cache_length(cfg, context_len)
        adt = dtype_of(cfg.activation_dtype)

        def attn_cache():
            hd = cfg.resolved_head_dim
            if cfg.mla is not None:
                m = cfg.mla
                return {
                    "c_kv": jax.ShapeDtypeStruct(
                        (batch_size, clen, m.kv_lora_rank), adt),
                    "k_rope": jax.ShapeDtypeStruct(
                        (batch_size, clen, m.qk_rope_head_dim), adt),
                    "pos": jax.ShapeDtypeStruct((batch_size, clen), jnp.int32),
                }
            G = cfg.num_kv_heads
            return {
                "k": jax.ShapeDtypeStruct((batch_size, clen, G, hd), adt),
                "v": jax.ShapeDtypeStruct((batch_size, clen, G, hd), adt),
                "pos": jax.ShapeDtypeStruct((batch_size, clen), jnp.int32),
            }

        def local_attn_cache():
            hd = cfg.resolved_head_dim
            G = cfg.num_kv_heads
            w = min(cfg.local_window, context_len)
            return {
                "k": jax.ShapeDtypeStruct((batch_size, w, G, hd), adt),
                "v": jax.ShapeDtypeStruct((batch_size, w, G, hd), adt),
                "pos": jax.ShapeDtypeStruct((batch_size, w), jnp.int32),
            }

        def cross_cache():
            hd = cfg.resolved_head_dim
            G = cfg.num_kv_heads
            n = cfg.encoder_seq_len or cfg.num_frontend_tokens
            return {
                "k": jax.ShapeDtypeStruct((batch_size, n, G, hd), adt),
                "v": jax.ShapeDtypeStruct((batch_size, n, G, hd), adt),
            }

        def block_cache(kind: str):
            if kind in ("attention", "moe_attention"):
                return local_attn_cache() if cfg.attention_kind == "local" \
                    else attn_cache()
            if kind == "cross_attention":
                return cross_cache()
            if kind == "encdec_attention":
                return {"self": attn_cache(), "cross": cross_cache()}
            if kind == "recurrent":
                w = cfg.lru_width or cfg.d_model
                cw = cfg.conv_width
                return {"h": jax.ShapeDtypeStruct((batch_size, w), jnp.float32),
                        "conv": jax.ShapeDtypeStruct(
                            (batch_size, cw - 1, w), adt)}
            if kind == "mlstm":
                inner = 2 * cfg.d_model
                nh = cfg.num_heads
                D = inner // nh
                cw = cfg.conv_width
                return {
                    "C": jax.ShapeDtypeStruct((batch_size, nh, D, D), jnp.float32),
                    "n": jax.ShapeDtypeStruct((batch_size, nh, D), jnp.float32),
                    "m": jax.ShapeDtypeStruct((batch_size, nh), jnp.float32),
                    "conv": jax.ShapeDtypeStruct(
                        (batch_size, cw - 1, inner), adt),
                }
            if kind == "slstm":
                d = cfg.d_model
                cw = cfg.conv_width
                f32 = jnp.float32
                return {
                    "c": jax.ShapeDtypeStruct((batch_size, d), f32),
                    "n": jax.ShapeDtypeStruct((batch_size, d), f32),
                    "h": jax.ShapeDtypeStruct((batch_size, d), f32),
                    "m": jax.ShapeDtypeStruct((batch_size, d), f32),
                    "conv": jax.ShapeDtypeStruct((batch_size, cw - 1, d), adt),
                }
            raise ValueError(kind)

        prefix, unit, n_groups, suffix = tfm.stack_plan(cfg)
        caches: Dict[str, Any] = {"prefix": {}, "suffix": {}}
        for i, kind in enumerate(prefix):
            caches["prefix"][f"l{i}"] = block_cache(kind)
        if n_groups:
            gc = {}
            for pos, kind in enumerate(unit):
                gc[f"b{pos}"] = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((n_groups, *s.shape), s.dtype),
                    block_cache(kind))
            caches["groups"] = gc
        for i, kind in enumerate(suffix):
            caches["suffix"][f"l{i}"] = block_cache(kind)
        return {"layers": caches,
                "cur": jax.ShapeDtypeStruct((batch_size,), jnp.int32)}


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg)


@functools.partial(jax.jit, static_argnums=0)
def _cast_to(dtype, leaves):
    return [x.astype(dtype) for x in leaves]


# ---------------------------------------------------------------------------
# input_specs for the dry-run
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this (arch, shape).

    train   -> kwargs for train_step(params, batch)
    prefill -> kwargs for serve_prefill(params, batch)
    decode  -> kwargs for serve_step(params, state, tokens)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    adt = dtype_of(cfg.activation_dtype)
    model = build_model(cfg)

    def frontend(batch_keys: Dict[str, Any]):
        if cfg.is_encoder_decoder:
            batch_keys["encoder_embeddings"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq_len, cfg.frontend_dim or cfg.d_model), adt)
        elif cfg.cross_attn_every > 0:
            batch_keys["frontend_embeddings"] = jax.ShapeDtypeStruct(
                (B, cfg.num_frontend_tokens, cfg.frontend_dim or cfg.d_model), adt)
        return batch_keys

    if shape.kind == "train":
        batch = frontend({
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "targets": jax.ShapeDtypeStruct((B, S), i32),
        })
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = frontend({"tokens": jax.ShapeDtypeStruct((B, S), i32)})
        return {"batch": batch}
    if shape.kind == "decode":
        state = model.init_decode_state_specs(B, S)
        if cfg.is_encoder_decoder or cfg.cross_attn_every > 0:
            pass  # cross caches already inside layer caches
        return {"state": state, "tokens": jax.ShapeDtypeStruct((B,), i32)}
    raise ValueError(shape.kind)
