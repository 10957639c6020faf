"""The readers of the program's own spans on the tiny cells: each reads a
value in the cells ``BENCHMARK.json`` lists it in, the parts add up to no
more than the benchmark's outside spans around them, and each reads nothing
where the program records no spans (an empty recorder, or a program without
``repro.obs.spans``)."""
import json
import statistics
import sys
import time

import pytest

from chip_bench_tiny import BENCH, ROOT, correct, driver, tiny_cell, tiny_ctx
from chiplib.harness import Reading, load_module

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PROGRAM_SPAN_READERS = [
    "ckpt_device_get_s", "ckpt_write_s", "ckpt_hash_s", "replicate_copy_s",
    "replicate_verify_s", "hash_passes", "restore_verify_s", "restore_read_s",
    "train_host_ms"]
READERS = {m["name"]: m["workloads"] for m in SPEC["per_layer"]
           if m["name"] in PROGRAM_SPAN_READERS}
CELLS = ["ckpt-replicate", "train-nosave"]


def read(name, reading):
    return load_module(BENCH / "metrics" / f"{name}.py", f"ps_{name}").read(reading)


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """One run of each tiny training cell, as its per-layer readers see it."""
    out = {}
    for name in CELLS:
        cell = tiny_cell(name)
        ctx = tiny_ctx(cell, tmp_path_factory.mktemp(name))
        res = driver(cell).run(ctx)
        assert correct(res), res["checks"]
        out[name] = Reading(ctx.spans, None, res["facts"], ctx.window,
                            cell.config, cell.workload, {})
    return out


def test_each_reader_has_a_program_span_entry():
    assert sorted(READERS) == sorted(PROGRAM_SPAN_READERS)
    assert all(m["source"] == "program_span" for m in SPEC["per_layer"]
               if m["name"] in READERS)
    assert READERS["train_host_ms"] == CELLS


@pytest.mark.parametrize("name,cell", [(n, c) for n, cells in READERS.items()
                                       for c in cells])
def test_reader_reads_the_programs_spans(name, cell, readings):
    value = read(name, readings[cell])
    assert value is not None and value > 0


def test_the_parts_fit_inside_the_outside_spans(readings):
    r = readings["ckpt-replicate"]
    v = {name: read(name, r) for name in list(READERS) + [
        "ckpt_save_s", "replicate_s", "restore_s"]}
    assert v["ckpt_device_get_s"] + v["ckpt_write_s"] + v["ckpt_hash_s"] \
        <= v["ckpt_save_s"]
    assert v["replicate_copy_s"] + v["replicate_verify_s"] <= v["replicate_s"]
    assert v["restore_verify_s"] + v["restore_read_s"] <= v["restore_s"]
    # five passes over the state, plus the files' headers and the small
    # files of a checkpoint, which at the tests' size are a few percent
    assert 5.0 < v["hash_passes"] < 5.5
    # the steps' host work, apart from the steps themselves
    steps = r.facts["window_steps"]
    assert 0 < v["train_host_ms"] * steps / 1e3 < r.facts["window_s"]


def _empty_recorder(monkeypatch):
    from repro.obs import spans
    monkeypatch.setattr(spans, "RECORDER", spans.Recorder())


def _no_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    import repro.obs
    monkeypatch.delattr(repro.obs, "spans", raising=False)


@pytest.mark.parametrize("program", [_empty_recorder, _no_recorder])
@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_program_spans(name, program, readings,
                                                    monkeypatch):
    program(monkeypatch)
    for cell in READERS[name]:
        assert read(name, readings[cell]) is None


def test_train_host_ms_is_the_median_step(monkeypatch):
    """A step whose feed held seconds of the benchmark's own work (a trace
    being stopped) does not move the reading; steps cut by the window's
    edges, without all three spans, are left out."""
    from repro.obs import spans
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    t0 = time.perf_counter()
    for step in range(5):
        for name in ("train.batch", "train.h2d", "train.dispatch"):
            if step == 4 and name == "train.dispatch":
                continue                          # the window closed here
            with rec.span(name, step=step):
                if step == 2 and name == "train.batch":
                    time.sleep(0.2)
    reading = Reading(None, None, {}, [t0, float("inf")], {}, {}, {})
    whole = [sum(r.seconds for r in rec.records() if r.attrs["step"] == k)
             for k in range(4)]
    assert read("train_host_ms", reading) == pytest.approx(
        1e3 * statistics.median(whole))
    assert read("train_host_ms", reading) < 1e3 * max(whole) / 10
