"""Cells of the chip benchmark cut to a size the CPU runs in seconds, for the
benchmark's own tests (widths here are test sizes, not the cells')."""
from __future__ import annotations

import copy
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chiplib import harness  # noqa: E402
from chiplib.harness import Cell, Context, load_module  # noqa: E402

TINY_MODEL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=96, num_hidden_layers=2, vocab_size=256)


def shrink(config: dict, workload: dict) -> None:
    """Cut a cell's configuration and traffic to the tests' size in place."""
    config.update(TINY_MODEL)
    workload.update(batch=4, seq=16)
    if "ckpt_every" in workload:
        workload["ckpt_every"] = 8


def tiny_cell(name: str, root: Path = ROOT) -> Cell:
    cell = copy.deepcopy(Cell.load(root, root / "benchmarks" / "chip", name))
    shrink(cell.config, cell.workload)
    return cell


def tiny_checkout(dest: Path, kind: str) -> Path:
    """A copy of ``BENCHMARK.json`` and the benchmark's files under ``dest``
    with every cell cut to the tests' size and peaks for ``kind``; returns
    the copy's benchmark directory."""
    bench = dest / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = Cell.load(ROOT, BENCH, w["name"])
        shrink(cell.config, cell.workload)
        (dest / [c["file"] for c in spec["configs"]
                 if c["name"] == w["config"]][0]).write_text(
            json.dumps(cell.config))
        (bench / "workloads" / f"{w['traffic']}.json").write_text(
            json.dumps(cell.workload))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks[kind] = dict(next(iter(peaks.values())), source="test")
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return bench


def tiny_ctx(cell: Cell, scratch: Path, seed: int = 2**31 + 17,
             seconds: float = 0.5) -> Context:
    import jax
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                  t0=time.perf_counter(), root=scratch, devices=jax.devices())
    ctx.spans.listen_for_compiles()
    return ctx


def driver(cell: Cell):
    name = cell.config["driver"]
    return load_module(BENCH / "drivers" / f"{name}.py", f"tiny_driver_{name}")


def correct(res: dict) -> bool:
    return harness.correct(res["checks"])
