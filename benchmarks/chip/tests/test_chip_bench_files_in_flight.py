"""The reader of ``replicate_files_in_flight`` on replications run here: a
little under 1 with one file after another, the pool's width with a pool, in
the tiny ckpt-replicate cell a value, and nothing where the program records
no spans (an empty recorder, or a program without ``repro.obs.spans``)."""
import json
import os
import sys
import time

import pytest

from chip_bench_tiny import BENCH, ROOT, correct, driver, tiny_cell, tiny_ctx
from chiplib.harness import Reading, load_module

NAME = "replicate_files_in_flight"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def read(reading):
    return load_module(BENCH / "metrics" / f"{NAME}.py", f"ps_{NAME}").read(
        reading)


def _replicate(root, monkeypatch, workers):
    """24 files of 1 MiB replicated from A to B and C on at most ``workers``
    threads; the reading of a window that holds both transfers."""
    import numpy as np

    import repro.core.transport as transport
    from repro.core.routes import Dataset
    from repro.obs import spans
    monkeypatch.setattr(spans, "RECORDER", spans.Recorder())
    monkeypatch.setattr(transport, "_MAX_WORKERS", workers)
    monkeypatch.setattr(transport.os, "cpu_count", lambda: workers)
    rng = np.random.default_rng(0)
    os.makedirs(os.path.join(root, "A", "ds", "sub"))
    for i in range(24):
        with open(os.path.join(root, "A", "ds", "sub" * (i % 2), f"f{i}"),
                  "wb") as f:
            f.write(rng.bytes(1 << 20))
    t0 = time.perf_counter()
    tr = transport.LocalFSTransport(str(root))
    for dest in ("B", "C"):
        st = tr.poll(tr.submit(Dataset("ds", 24 << 20, 24, 2), "A", dest))
        assert st.files_done == 24
    return Reading(None, None, {}, [t0, float("inf")], {}, {}, {})


def test_entry_is_the_replications_and_reads_ckpt_replicate():
    (m,) = [m for m in SPEC["per_layer"] if m["name"] == NAME]
    assert m["source"] == "program_span" and m["moves"] == "train_tokens_per_s"
    assert m["layer"] == "checkpoint replication"
    assert m["workloads"] == ["ckpt-replicate"]


def test_one_file_after_another_reads_about_one(tmp_path, monkeypatch):
    value = read(_replicate(tmp_path, monkeypatch, workers=1))
    assert 0.8 < value <= 1.0


def test_a_pool_reads_above_one(tmp_path, monkeypatch):
    value = read(_replicate(tmp_path, monkeypatch, workers=4))
    assert 2.0 < value <= 4.0


def test_reads_the_tiny_ckpt_replicate_cell(tmp_path):
    cell = tiny_cell("ckpt-replicate")
    ctx = tiny_ctx(cell, tmp_path)
    res = driver(cell).run(ctx)
    assert correct(res), res["checks"]
    reading = Reading(ctx.spans, None, res["facts"], ctx.window, cell.config,
                      cell.workload, {})
    assert 0 < read(reading) <= 8


def _empty_recorder(monkeypatch):
    from repro.obs import spans
    monkeypatch.setattr(spans, "RECORDER", spans.Recorder())


def _no_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    import repro.obs
    monkeypatch.delattr(repro.obs, "spans", raising=False)


@pytest.mark.parametrize("program", [_empty_recorder, _no_recorder])
def test_reads_nothing_without_program_spans(program, monkeypatch):
    program(monkeypatch)
    assert read(Reading(None, None, {}, [0.0, float("inf")], {}, {}, {})) \
        is None
