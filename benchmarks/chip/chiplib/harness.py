"""One run of one cell: find its files by name, check the chip, drive the
cell's driver, reduce what it recorded, print the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the workload entry names its configuration and its traffic;
* ``configs[].file`` is the configuration; its ``driver`` key names
  ``drivers/<driver>.py`` and its ``reference`` key a file beside it;
* ``workloads/<traffic>.json`` holds the traffic's parameters;
* ``limits/<cell>.json`` holds the limit of each number ``correct`` compares;
* ``metrics/<metric>.py`` reads one per-layer metric (``read(reading)``,
  ``None`` where it finds nothing to read);
* ``peaks.json`` holds each chip's peaks, keyed by ``device_kind``.

A driver's ``run(ctx)`` returns ``setup_s``, ``metrics`` (end-to-end values),
``checks`` (``{name: (value, limit)}``, each correct when ``value <= limit``),
``attempted``, ``failed`` and ``facts``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

from chiplib.spans import ANNOTATION_PREFIX, Spans

EXIT_NO_CHIP = 3


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with everything it names, loaded."""
    name: str
    entry: dict
    config: dict
    config_path: Path
    workload: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, root: Path, bench: Path, name: str) -> "Cell":
        spec = json.loads((root / "BENCHMARK.json").read_text())
        entry = next((w for w in spec["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
        config_path = root / conf["file"]

        def mine(metric):
            return name in metric.get("workloads", [name])

        return cls(
            name=name, entry=entry,
            config=json.loads(config_path.read_text()), config_path=config_path,
            workload=json.loads(
                (bench / "workloads" / f"{entry['traffic']}.json").read_text()),
            limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
            end_to_end=[m for m in spec["end_to_end"] if mine(m)],
            per_layer=[m for m in spec["per_layer"] if mine(m)])


@dataclass
class Context:
    """What a driver gets: the cell's files, the run's arguments, the spans,
    and the calls that mark the window, the trace and the memory peak."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float
    root: Path
    devices: list = field(default_factory=list)
    spans: Spans = field(default_factory=Spans)
    window: list = field(default_factory=lambda: [None, None])
    trace_facts: dict = field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None
    _annotation: object = None

    @property
    def workload(self) -> dict:
        return self.cell.workload

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def config_path(self) -> Path:
        return self.cell.config_path

    @property
    def limits(self) -> dict:
        return self.cell.limits

    def work_dir(self) -> str:
        """A fresh directory for this cell under the checkout's ``bench_out``;
        the driver removes it when the run ends."""
        path = self.root / "bench_out" / f"work-{self.cell.name}"
        shutil.rmtree(path, ignore_errors=True)
        path.mkdir(parents=True)
        return str(path)

    @property
    def trace_dir(self) -> str:
        return str(self.root / "bench_out" / f"trace-{self.cell.name}")

    def window_opened(self, t: float) -> None:
        self.window[0] = t

    def window_closed(self, t: float) -> None:
        self.window[1] = t

    def trace_start(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + "traced_window")
        self._annotation.__enter__()

    def trace_stop(self, **facts) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._annotation = None
        self.trace_facts.update(facts)

    def read_peak(self) -> None:
        """The device's peak bytes in use so far, on the fullest chip."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


@dataclass
class Reading:
    """What a per-layer metric's reader gets."""
    spans: Spans
    trace: object                # chiplib.trace.Reduction, or None
    facts: dict
    window: list
    config: dict
    workload: dict
    peak: dict


# ---------------------------------------------------------------------- run
def correct(checks: dict) -> bool:
    """A run is correct when every number it compared is within its limit."""
    return all(value <= limit for value, limit in checks.values())


def io_counters() -> dict:
    """This process's ``write_bytes`` and ``cancelled_write_bytes`` (bytes it
    dirtied for storage, and those dropped before they reached it), and the
    bytes each block device has written so far."""
    out = {}
    try:
        with open("/proc/self/io") as f:
            for line in f:
                key, _, value = line.partition(":")
                if key in ("write_bytes", "cancelled_write_bytes"):
                    out[key] = int(value)
        with open("/proc/diskstats") as f:
            for line in f:
                parts = line.split()
                if len(parts) > 9 and not parts[2].startswith(("loop", "ram")):
                    out[f"disk:{parts[2]}"] = 512 * int(parts[9])
    except OSError:
        pass
    return out


def io_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if k in before and after[k] != before[k]}


def filesystem_of(path: str) -> Optional[str]:
    """The type of the filesystem that holds ``path``, from /proc/mounts."""
    path, best = os.path.realpath(path), ("", None)
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mnt, fstype = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, fstype)
    except OSError:
        return None
    return best[1]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at one fixed place in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(need: int, root: Path):
    """The accelerators JAX finds, or ``None`` (with the reason on stderr)
    when they are not TPUs or fewer than the cell asks for.  The TPU runtime
    keeps its logs in the checkout, not in a fixed directory of the host."""
    os.environ.setdefault("TPU_LOG_DIR", str(root / "bench_out" / "tpu_logs"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return None
    if len(devices) < need:
        print(f"chip benchmark: the cell needs {need} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def main(argv, t0: float, root: Path, bench: Path) -> int:
    args = parse_args(argv)
    cell = Cell.load(root, bench, args.workload)
    devices = find_chips(cell.entry["chips"], root)
    if devices is None:
        return EXIT_NO_CHIP
    peaks = json.loads((bench / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"chip benchmark: no peaks for device_kind {kind!r} in "
              f"peaks.json", file=sys.stderr)
        return EXIT_NO_CHIP
    cache = enable_compile_cache(root)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t0=t0, root=root,
                  devices=devices[:cell.entry["chips"]])
    ctx.spans.listen_for_compiles()
    io0 = io_counters()
    driver = load_module(bench / "drivers" / f"{cell.config['driver']}.py",
                         f"bench_driver_{cell.config['driver']}")
    res = driver.run(ctx)
    facts = dict(res.get("facts", {}))
    facts.update(compile_cache=cache, run_s=time.perf_counter() - t0,
                 compiles=len(ctx.spans.compiles),
                 compile_cache_misses=ctx.spans.counters.get(
                     "compile_cache_misses", 0),
                 bytes_written=io_delta(io0, io_counters()),
                 work_filesystem=filesystem_of(str(root / "bench_out")))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": None, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": {}, "device": device}
    if ctx.trace:
        from chiplib.trace import find_xplane, reduce_xplane
        red = reduce_xplane(find_xplane(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        facts.update(ctx.trace_facts, compiles_in_traced_window=red.compiles)
        reading = Reading(ctx.spans, red, facts, ctx.window, cell.config,
                          cell.workload, peaks[kind])
        for m in cell.per_layer:
            reader = load_module(bench / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(reading)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = red.breakdown()
    else:
        values = dict(res["metrics"], setup_s=res["setup_s"])
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in res["checks"].items()}
    line["correct"] = correct(res["checks"])
    line["checks"] = checks
    print(json.dumps({"facts": facts}, default=str), flush=True)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
