"""A tiny copy of the benchmark for the CPU tests: the real BENCHMARK.json
and bench/ in a temporary root, plus a small configuration, mixes and cells
that are added as files and entries only."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SIZES = {"layers": 2, "d_model": 128, "heads": 4, "kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab": 512, "window": 32,
              "glu": True, "rope_theta": 10000.0, "norm_eps": 1e-6,
              "param_dtype": "float32", "activation_dtype": "bfloat16"}
TINY_CAMPAIGN = {"trials_per_task": 2, "tuning_seed": 0,
                 "source_programs_per_task": 2, "pretrain_epochs": 1,
                 "moses": {"online_epochs": 1, "adaptation_epochs": 1,
                           "population_size": 8, "evolution_rounds": 1,
                           "top_k_measure": 2}}
CELLS = {
    "tiny.kset-prefill": ("kset-tiny", {
        "driver": "kset", "phase": "prefill", "batch": 1, "seq": 128,
        "campaign": TINY_CAMPAIGN, "trace_seconds": 0.1},
        {"matmul_rel_err": 0.02, "attention_rel_err": 0.02}),
    "tiny.serve": ("serve-tiny", {
        "driver": "serve", "wave": 2, "prompt_lens": [8, 16],
        "new_tokens": [12, 24], "max_len": 48, "check_requests": 2,
        "trace_seconds": 0.1},
        {"served_logit_gap": 0.1}),
}


def fake_device(chips: int) -> dict:
    """Stands in for the harness's look for a TPU."""
    import harness
    return {"platform": "cpu", "kind": "cpu", "count": 1,
            "peaks": harness.load_peaks()["TPU v5 lite"]}


def make_root(tmp: str) -> str:
    """A checkout in `tmp` with the tiny configuration, mixes and cells
    added as new files and entries of BENCHMARK.json."""
    root = os.path.join(tmp, "root")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump({"source": "test", "program": {"arch": "h2o-danube-1.8b"},
                   "sizes": TINY_SIZES}, f)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "CPU test"})
    for cell, (mix, traffic, limits) in CELLS.items():
        with open(os.path.join(b, "traffic", mix + ".json"), "w") as f:
            json.dump(traffic, f)
        with open(os.path.join(b, "limits", cell + ".json"), "w") as f:
            json.dump(limits, f)
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "test"})
    by_name = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    for name, cells in (("kset_ms", ["tiny.kset-prefill"]),
                        ("decode_tok_s", ["tiny.serve"]),
                        ("tpot_p95_ms", ["tiny.serve"]),
                        ("prefill_tok_s", ["tiny.serve"])):
        by_name[name]["workloads"] += cells
    for m in spec["per_layer"]:
        if m["moves"] == "kset_ms":
            m["workloads"].append("tiny.kset-prefill")
        if m["moves"] in ("decode_tok_s", "prefill_tok_s"):
            m["workloads"].append("tiny.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def run_cell(root: str, cell: str, seed: int = 7, trace: int = 0,
             seconds: float = 0.0, capsys=None) -> dict:
    import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  device_check=fake_device, root=root, interpret=True)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
