"""The one generator that turns a traffic file's parameters into work.

Two kinds of work, named by the traffic file's `driver`:

kset   A model's kernel set: the kernel calls of one prefill (`phase:
       prefill`, `batch` x `seq` tokens) or one decode step (`phase:
       decode`, `batch` rows) of a decoder with the configuration's sizes,
       each layer's calls repeated `layers` times, the head once.

serve  Whole waves of `wave` greedy requests with no end token. Slot i of
       wave w asks for a prompt of `prompt_lens[(w * wave + i) % n]` tokens
       and `new_tokens` spread evenly over [lo, hi] by slot. The seed only
       shuffles the slots of each wave and draws the token ids, so every
       seed gets the same sizes in another order and does the same work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def kset_calls(s: dict, t: dict) -> List[Dict]:
    """The distinct calls of one pass, in the order a layer makes them;
    `count` is how often the pass makes each."""
    d, H, G, hd, ff, V, L = (s["d_model"], s["heads"], s["kv_heads"],
                             s["head_dim"], s["d_ff"], s["vocab"],
                             s["layers"])
    ff_in = ff * (2 if s["glu"] else 1)
    if t["phase"] == "prefill":
        rows = t["batch"] * t["seq"]
    elif t["phase"] == "decode":
        rows = t["batch"]
    else:
        raise ValueError(f"unknown kset phase {t['phase']!r}")
    calls = [dict(name="qkv", kind="matmul", dims=(rows, (H + 2 * G) * hd, d),
                  count=L)]
    if t["phase"] == "prefill":
        # the only attention kernel is for a whole sequence (no decode one)
        calls.append(dict(name="attn", kind="attention",
                          dims=(t["batch"], H, G, t["seq"], hd,
                                s["window"]), count=L))
    calls += [
        dict(name="out", kind="matmul", dims=(rows, d, H * hd), count=L),
        dict(name="ffn_in", kind="matmul", dims=(rows, ff_in, d), count=L),
        dict(name="ffn_out", kind="matmul", dims=(rows, d, ff), count=L),
        dict(name="lm_head", kind="matmul", dims=(t["batch"], V, d),
             count=1),
    ]
    return calls


def pass_order(calls: List[Dict]) -> List[Dict]:
    """One pass: every per-layer call in layer order, then the rest."""
    layers = max(c["count"] for c in calls)
    per_layer = [c for c in calls if c["count"] == layers]
    once = [c for c in calls if c["count"] != layers]
    return per_layer * layers + [c for c in once for _ in range(c["count"])]


def wave_sizes(t: dict, w: int) -> List[Tuple[int, int]]:
    """(prompt length, new tokens) of each slot of wave w, before shuffling."""
    n, lens = t["wave"], t["prompt_lens"]
    lo, hi = t["new_tokens"]
    return [(lens[(w * n + i) % len(lens)],
             lo + round((hi - lo) * i / max(n - 1, 1))) for i in range(n)]


def serve_wave(t: dict, vocab: int, seed: int, w: int
               ) -> List[Tuple[np.ndarray, int]]:
    """Wave w for a seed: (prompt token ids, new tokens) per slot."""
    rng = np.random.default_rng([seed, w])
    sizes = wave_sizes(t, w)
    lens = [p for p, _ in sizes]
    news = [m for _, m in sizes]
    lens = [lens[i] for i in rng.permutation(len(lens))]
    news = [news[i] for i in rng.permutation(len(news))]
    # id 0 is what the engine pads with; prompts never use it
    return [(rng.integers(1, vocab, size=p, dtype=np.int32), m)
            for p, m in zip(lens, news)]


def padded_lengths(t: dict, waves: int) -> List[int]:
    """The distinct prompt widths (each wave's longest) of the first waves:
    the prefill shapes a run compiles."""
    return sorted({max(p for p, _ in wave_sizes(t, w)) for w in range(waves)})
