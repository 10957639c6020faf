#!/usr/bin/env python3
"""The readings that each limit of ``correct`` is set from, at a cell's size.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds <n> ... --control-seeds <n> ...

For each of ``--seeds``, the program's numbers: its first three steps through
the job's own loop, feed and seams, against the float32 reference.  For each
of ``--control-seeds``, the numbers of the control (the reference in float8 in
the program's place) and of a fault planted in the reference (half of each
batch left out, the mean taken over the rest).  One JSON line per reading.
The benchmark's runs do not run this; it needs the chip as they do.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chiplib.harness import (Cell, Context, enable_compile_cache,  # noqa: E402
                             find_chips, load_module)


def first_steps_cell(cell: Cell) -> Cell:
    """The cell's job cut to its first steps: nothing saved, a window of one
    step opened after the three that are compared."""
    cell = copy.deepcopy(cell)
    cell.workload = {k: v for k, v in cell.workload.items()
                     if k not in ("ckpt_every",)}
    cell.workload.update(warm_steps=3, trace_steps=1)
    return cell


def train_readings(ctx, drv, seeds, control_seeds):
    ref = drv.load_reference(ctx)
    cell = first_steps_cell(ctx.cell)
    for seed in seeds:
        c = Context(cell=cell, seed=seed, seconds=0.0, trace=False,
                    t0=time.perf_counter(), root=ctx.root, devices=ctx.devices)
        res = drv.run(c)
        yield {"seed": seed, "side": "program",
               **{k: v for k, (v, _) in res["checks"].items()}}
    for seed in control_seeds:
        wseed = drv.derived_seed(seed, 1)
        job = drv.Job(ctx, ctx.workload, data_seed=drv.derived_seed(seed, 2))
        batches = [job.batch(k) for k in range(drv.N_COMPARED_STEPS)]
        opt = ctx.config["optimizer"]
        f32 = ref.train_steps(ctx.config, opt, wseed, batches)
        for side, kw in (("control_fp8", {"quant": "fp8"}),
                         ("fault_half_batch",
                          {"rows": ctx.workload["batch"] // 2})):
            got = ref.train_steps(ctx.config, opt, wseed, batches, **kw)
            nums = drv.compare(got, f32, ctx.limits)
            yield {"seed": seed, "side": side,
                   **{k: v for k, (v, _) in nums.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, BENCH, args.workload)
    devices = find_chips(cell.entry["chips"], ROOT)
    if devices is None:
        return 3
    enable_compile_cache(ROOT)
    ctx = Context(cell=cell, seed=0, seconds=0.0, trace=False, t0=T0,
                  root=ROOT, devices=devices[:cell.entry["chips"]])
    driver = cell.config["driver"]
    drv = load_module(BENCH / "drivers" / f"{driver}.py", f"bench_driver_{driver}")
    for row in train_readings(ctx, drv, args.seeds, args.control_seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
