"""The harness finds every cell's files by name, a new configuration, mix
and metric are taken as added files and entries only, and a run without a
TPU or with an unknown device fails before printing a result."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny
import harness
import run

ROOT, BENCH = tiny.ROOT, tiny.BENCH
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell, ROOT)
    assert c.chips == 1 and callable(c.driver.run)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert all(callable(r.read) for r in c.readers.values())
    assert c.limits and all(v > 0 for v in c.limits.values())


def test_benchmark_json_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and os.path.exists(
            os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_setup_config_widths_match_the_program():
    from repro.configs import get_config
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        s, p = cfg["sizes"], get_config(cfg["program"]["arch"])
        assert (s["layers"], s["d_model"], s["heads"], s["kv_heads"],
                s["head_dim"], s["d_ff"], s["vocab"]) == (
            p.num_layers, p.d_model, p.num_heads, p.num_kv_heads,
            p.resolved_head_dim, p.d_ff, p.vocab_size)
        assert s["param_dtype"] == p.param_dtype


def test_additions_are_files_and_entries_only(tmp_path, capsys):
    """A new configuration, mix, cell and per-layer metric, added as files
    and entries, are taken without editing any file the benchmark has."""
    root = tiny.make_root(str(tmp_path))
    before = {p: open(os.path.join(BENCH, p), "rb").read()
              for p in ("harness.py", "run.py", "mixes.py")}
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "metrics", "tiny.waves.py"), "w") as f:
        f.write("def read(ctx, out, trace):\n"
                "    return float(out.counts['waves'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["per_layer"].append({
        "name": "tiny.waves", "unit": "waves", "better": "higher",
        "source": "host_clock", "layer": "serving engine",
        "moves": "decode_tok_s", "workloads": ["tiny.serve"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = harness.resolve("tiny.serve", root)
    assert "tiny.waves" in cell.readers
    assert cell.config["sizes"] == tiny.TINY_SIZES
    line = tiny.run_cell(root, "tiny.serve", trace=0, capsys=capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "decode_tok_s",
                                    "tpot_p95_ms", "prefill_tok_s"}
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == 1
    for p, data in before.items():
        assert open(os.path.join(BENCH, p), "rb").read() == data


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v0 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.BenchError):
        harness.check_device(1)
    rc = run.main(["--workload", SPEC["workloads"][0]["name"], "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_too_few_chips_is_an_error(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.BenchError):
        harness.check_device(4)


@pytest.mark.parametrize("with_program", [True, False])
def test_host_without_tpu_exits_without_a_result(tmp_path, with_program):
    """On a CPU host, and in a directory holding only BENCHMARK.json and
    bench/, a run exits non-zero and prints nothing on standard output."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        shutil.copytree(os.path.join(ROOT, "src"), root / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
