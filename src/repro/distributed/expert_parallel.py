"""Expert-parallel MoE via shard_map (the DeepSpeed-MoE / GShard EP pattern).

Baseline ("scatter") lets GSPMD partition a global scatter/gather dispatch —
measured pathological at 256 experts (EXPERIMENTS.md §Perf: compute replicated
across the model axis). This path makes the parallelism explicit:

  - tokens stay sharded over the data axes (every model shard sees the same
    local tokens);
  - each model shard owns E/tp experts and K-selects ITS tokens for ITS
    experts with a LOCAL capacity buffer (no global cumsum, no cross-shard
    scatter): `models.moe.held_experts_ffn`, the function a chip that
    holds a share of the experts runs on its own;
  - one psum over the model axis combines expert outputs (each token's top-k
    experts live on different shards) — the same wire cost as a Megatron
    row-parallel matmul.

Expert weights may additionally be fsdp-sharded on their embed dim; they are
all-gathered just-in-time inside the shard (ZeRO-3 semantics).

Capacity note: capacity is per (token-shard, expert): C_loc =
ceil(T_local * top_k * cf / E) — statistically equivalent to the global
capacity for shuffled tokens; correctness vs the dense oracle is tested with
a generous capacity factor.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import PartitionSpec as P


def moe_forward_expert_parallel(p, cfg, x: jax.Array, hints
                                ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d]. Requires act_sharding hints (mesh + axes)."""
    from repro.models.moe import _router, _shared_ffn, held_experts_ffn

    mo = cfg.moe
    mesh = hints.mesh
    tp = hints.tp
    dp = hints.dp
    E = mo.num_experts
    tp_size = mesh.shape[tp]
    assert E % tp_size == 0, (E, tp_size)
    E_loc = E // tp_size

    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    weights, idx, aux = _router(p, cfg, xf)

    dp_size = hints.axis_size("dp")
    T_loc = T // max(dp_size, 1)
    C_loc = max(1, int(math.ceil(T_loc * mo.top_k * mo.capacity_factor / E)))

    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    xspec = P(dp_entry, None)
    rspec = P(dp_entry, None)
    # expert weights: [E@tp, d(@dp if fsdp), f]
    wspec = P(tp, dp_entry if cfg.sharding_plan == "fsdp_tp" else None, None)
    wospec = P(tp, None, dp_entry if cfg.sharding_plan == "fsdp_tp" else None)

    use_glu = "wg" in p
    assert use_glu, "expert-parallel path expects GLU experts (all our MoE archs)"

    def body(xf_, w_, i_, wi_, wg_, wo_):
        sid = jax.lax.axis_index(tp)
        if cfg.sharding_plan == "fsdp_tp" and dp:
            wi_ = jax.lax.all_gather(wi_, dp, axis=1, tiled=True)
            wg_ = jax.lax.all_gather(wg_, dp, axis=1, tiled=True)
            wo_ = jax.lax.all_gather(wo_, dp, axis=2, tiled=True)
        y = held_experts_ffn(cfg, xf_, w_, i_, wi_, wg_, wo_, sid * E_loc,
                             C_loc)
        return jax.lax.psum(y, tp)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, rspec, rspec, wspec, wspec, wospec),
        out_specs=P(dp_entry, None),
        check_vma=False,
    )(xf, weights, idx, p["wi"], p["wg"], p["wo"])

    if mo.num_shared_experts > 0:
        y = y + _shared_ffn(p, cfg, xf)
    return y.reshape(B, S, d), aux
