"""Exact operation and byte counts of the work the benchmark drives.

Kept with the benchmark, not taken from the program: a change to the
program cannot move the yardstick. Counts are of the work the algorithm
needs, from the shapes alone: a kernel that does more (masked blocks it
still computes, padding) spends time the count does not pay for.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

BF16, F32 = 2, 4


def matmul(M: int, N: int, K: int, out_bytes: int, in_bytes: int = BF16
           ) -> Tuple[float, float]:
    """(flops, least HBM bytes) of C[M,N] = A[M,K] @ B[K,N]: each operand
    read once, the result written once."""
    return 2.0 * M * N * K, float(in_bytes * (M * K + K * N)
                                  + out_bytes * M * N)


def causal_pairs(S: int, window: int = 0) -> int:
    """Query-key pairs a causal mask (with a sliding window of `window`
    keys, 0 for none) leaves unmasked over a sequence of S."""
    if window <= 0 or window >= S:
        return S * (S + 1) // 2
    # rows q < window see q + 1 keys; later rows see `window` keys
    return window * (window + 1) // 2 + (S - window) * window


def attention(batch: int, heads: int, kv_heads: int, S: int, D: int,
              window: int = 0, in_bytes: int = BF16, out_bytes: int = F32
              ) -> Tuple[float, float]:
    """(flops, least HBM bytes) of causal (windowed) attention: QK^T and PV
    over the unmasked pairs only; Q and the output at every query head, K
    and V at the model's own KV heads, each moved once."""
    flops = 4.0 * batch * heads * D * causal_pairs(S, window)
    nbytes = (in_bytes * batch * heads * S * D
              + 2 * in_bytes * batch * kv_heads * S * D
              + out_bytes * batch * heads * S * D)
    return flops, float(nbytes)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# ----------------------------------------------------------- model sizes

def layer_weights(s: dict) -> int:
    """Parameters of one decoder layer's matrices (norm scales aside)."""
    d, hd = s["d_model"], s["head_dim"]
    H, G, ff = s["heads"], s["kv_heads"], s["d_ff"]
    return d * (H + 2 * G) * hd + H * hd * d + (3 if s["glu"] else 2) * d * ff


def model_weights(s: dict) -> int:
    """Every parameter: layers, norms, embedding and head."""
    d = s["d_model"]
    return (s["layers"] * (layer_weights(s) + 2 * d) + d
            + 2 * s["vocab"] * d)


def prefill_flops(s: dict, n: int) -> float:
    """Forward FLOPs a prompt of n real tokens needs: every layer's matrices
    at every token, causal windowed attention over the unmasked pairs, and
    the head at the last position only (what prefill returns)."""
    L, H, hd = s["layers"], s["heads"], s["head_dim"]
    return (2.0 * n * L * layer_weights(s)
            + 4.0 * L * H * hd * causal_pairs(n, s["window"])
            + 2.0 * s["d_model"] * s["vocab"])


def decode_step(s: dict, contexts: Iterable[int], param_bytes: int
                ) -> Tuple[float, float]:
    """(flops, least bytes) of one decode step for the rows still producing
    tokens, each attending over `context` cached positions (window-capped):
    every stored layer and head weight read once, the embedding rows of the
    batch, the cached K/V of each row read and its new K/V written."""
    ctx: List[int] = [min(c, s["window"]) if s["window"] else c
                      for c in contexts]
    B = len(ctx)
    L, H, G, hd, d = (s["layers"], s["heads"], s["kv_heads"], s["head_dim"],
                      s["d_model"])
    kv_bytes = BF16 * 2 * G * hd * L
    flops = (2.0 * B * (L * layer_weights(s) + d * s["vocab"])
             + 4.0 * L * H * hd * sum(ctx))
    # every weight but the embedding table, of which only B rows are read
    weights = (model_weights(s) - s["vocab"] * d + B * d) * param_bytes
    return flops, float(weights + kv_bytes * (sum(ctx) + B))

