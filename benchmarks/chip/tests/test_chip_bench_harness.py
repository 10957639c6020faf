"""The chip benchmark's harness on the CPU: it refuses to run without a TPU,
finds every file by name, takes a new cell as files only, and counts model
FLOPs as a hand count does."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chip_bench_tiny import (BENCH, ROOT, correct, driver, tiny_checkout,
                             tiny_ctx)
from chiplib.flops import checksum_bytes, llama_train_flops_per_token
from chiplib import harness
from chiplib.harness import Cell, load_module

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_exits_nonzero_with_only_the_benchmark(cell, tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files has
    no program to run: past the look for a chip, the run fails and prints
    no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks" / "chip"
    script = (
        "import sys, jax; from pathlib import Path\n"
        f"sys.path.insert(0, {str(bench)!r})\n"
        "from chiplib import harness\n"
        "harness.find_chips = lambda need, root: jax.devices()\n"
        "harness.enable_compile_cache = lambda root: 'off'\n"
        "kind = jax.devices()[0].device_kind\n"
        "harness.json.loads = (lambda f: lambda s: {kind: {}, **f(s)})"
        "(harness.json.loads)\n"
        f"sys.exit(harness.main(['--workload', {cell!r}, '--seed', '1', "
        "'--seconds', '1', '--trace', '0'], 0.0, "
        f"Path({str(tmp_path)!r}), Path({str(bench)!r})))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                         env=dict(env, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "No module named 'repro'" in out.stderr


def _unchanged_state(*a, **kw):
    def step(params, opt, batch):
        import jax.numpy as jnp
        return params, opt, jnp.float32(5.5), {}
    return step


@pytest.mark.parametrize("broken", [False, True])
def test_a_run_past_the_chip_check_prints_its_line(broken, tmp_path,
                                                   monkeypatch, capsys):
    """The harness's run without its look for a chip: the result line has
    the contract's keys, the checks come last, and a step that returns its
    state unchanged makes ``correct`` false."""
    import jax

    import repro.train.loop as loop
    bench = tiny_checkout(tmp_path, jax.devices()[0].device_kind)
    monkeypatch.setattr(harness, "find_chips", lambda need, root: jax.devices())
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    if broken:
        monkeypatch.setattr(loop, "make_train_step", _unchanged_state)
    rc = harness.main(["--workload", "ckpt-replicate", "--seed", str(2**31 + 9),
                       "--seconds", "0.3", "--trace", "0"], 0.0, tmp_path, bench)
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "checks"
    assert line["correct"] is (not broken)
    assert set(line["metrics"]) == {"train_tokens_per_s", "resume_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = Cell.load(ROOT, BENCH, cell)
    assert (BENCH / "drivers" / f"{c.config['driver']}.py").is_file()
    if "reference" in c.config:
        assert (c.config_path.parent / c.config["reference"]).is_file()
    assert c.config["name"] == c.entry["config"]
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    reported = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py", m["name"])
        assert callable(reader.read)
        assert m["moves"] in reported, (m["name"], cell)


def test_every_config_is_used_and_named_once():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    assert set(names) == {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_a_cell_added_as_files_only_is_found_and_runs(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, limits and a
    per-layer metric as new files and new entries, and run the new cell."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks" / "chip"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    src = Cell.load(ROOT, BENCH, "train-nosave")
    cfg = dict(src.config, name="fixture-lm", num_hidden_layers=2,
               hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, vocab_size=256)
    (bench / "configs" / "fixture-lm.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "fixture-mix.json").write_text(
        json.dumps(dict(src.workload, batch=2, seq=8)))
    (bench / "limits" / "fixture-cell.json").write_text(json.dumps(src.limits))
    (bench / "metrics" / "fixture_steps.py").write_text(
        "def read(r):\n    return r.facts.get('window_steps')\n")
    spec["configs"].append({"name": "fixture-lm", "source": "fixture",
                            "file": "benchmarks/chip/configs/fixture-lm.json",
                            "reduced": [], "why": "fixture"})
    spec["workloads"].append({"name": "fixture-cell", "config": "fixture-lm",
                              "traffic": "fixture-mix", "chips": 1,
                              "why": "fixture"})
    spec["per_layer"].append({"name": "fixture_steps", "unit": "steps",
                              "better": "higher", "source": "program_counter",
                              "layer": "model step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["fixture-cell"]})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("fixture-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = Cell.load(tmp_path, bench, "fixture-cell")
    assert cell.config["num_hidden_layers"] == 2
    assert [m["name"] for m in cell.per_layer] == ["fixture_steps"]
    assert "train_tokens_per_s" in {m["name"] for m in cell.end_to_end}
    res = driver(cell).run(tiny_ctx(cell, tmp_path, seconds=0.3))
    assert correct(res) and res["metrics"]["train_tokens_per_s"] > 0
    reader = load_module(bench / "metrics" / "fixture_steps.py", "fixture_steps")
    reading = type("R", (), {"facts": res["facts"]})
    assert reader.read(reading) == res["facts"]["window_steps"] > 0


def test_model_flops_match_a_hand_count_for_smollm_135m():
    cfg = json.loads((BENCH / "configs" / "smollm-135m.json").read_text())
    d, ff, layers, vocab, hd = 576, 1536, 30, 49152, 64
    attn = d * hd * 9 * 2 + d * hd * 3 * 2      # q and o; k and v (3 kv heads)
    mlp = 3 * d * ff
    params = layers * (attn + mlp) + vocab * d  # the tied head counts once
    assert params == 134_479_872
    attention = 12 * layers * 9 * hd * 512
    assert llama_train_flops_per_token(cfg, 512) == 6 * params + attention
    assert checksum_bytes(1 << 28) == 1 << 30
