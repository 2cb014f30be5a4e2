"""A tiny DeepSeek-V3-shaped configuration and a `serve_moe` cell for the
CPU tests, added to a `tiny.make_root` checkout as files and entries only."""
from __future__ import annotations

import json
import os

import tiny

CONFIG = {
    "source": "test", "program": {"arch": "deepseek-v3-671b"},
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "vocab_size": 500, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6,
    "deployment": {"routed_experts": 16, "first_expert": 4,
                   "param_dtype": "float32",
                   "activation_dtype": "bfloat16"},
}
CELL = "tiny.serve-moe"
TRAFFIC = {"driver": "serve_moe", "wave": 4, "prompt_lens": [8, 16],
           "new_tokens": [6, 12], "max_len": 32, "prefill_rows": 2,
           "check_requests": 2, "trace_seconds": 0.1}
LIMITS = {"served_logit_gap_mean": 0.01}


def add_cell(root: str) -> str:
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-moe.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(b, "traffic", "serve-moe-tiny.json"), "w") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(b, "limits", CELL + ".json"), "w") as f:
        json.dump(LIMITS, f)
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-moe",
                              "traffic": "serve-moe-tiny", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "dsv3-ep32.serve-decode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def make_root(tmp: str) -> str:
    return add_cell(tiny.make_root(tmp))
