"""Each driver runs its cell's loop at a tiny size on the CPU and comes out
correct; with the timed path broken underneath, or the control in the
program's place, it comes out not correct."""
import os

import numpy as np
import pytest

from chip_bench_tiny import (BENCH, ROOT, Cell, correct, driver, harness,
                             tiny_cell, tiny_ctx)

TRAINING_CELLS = ["ckpt-replicate", "train-nosave"]


@pytest.fixture
def scratch(tmp_path):
    return tmp_path


@pytest.mark.parametrize("name", TRAINING_CELLS)
def test_driver_runs_its_cell_at_a_tiny_size(name, scratch):
    cell = tiny_cell(name)
    res = driver(cell).run(tiny_ctx(cell, scratch))
    assert correct(res), res["checks"]
    assert res["attempted"] > 0 and res["setup_s"] > 0
    assert res["facts"]["compiles_in_window"] == 0
    assert all(v > 0 for v in res["metrics"].values())
    assert not (scratch / "bench_out" / f"work-{name}").exists()


def test_ckpt_window_ends_with_a_save(scratch):
    cell = tiny_cell("ckpt-replicate")
    ctx = tiny_ctx(cell, scratch)
    res = driver(cell).run(ctx)
    every, warm = cell.workload["ckpt_every"], cell.workload["warm_steps"]
    assert (res["attempted"] + warm) % every == 0
    assert res["facts"]["saves_in_window"] == (res["attempted"] + warm) // every


def test_feed_is_a_function_of_seed_and_step():
    drv = driver(tiny_cell("train-nosave"))
    a = drv.make_batch(2**31 + 5, 3, 4, 32, 256, 0.1, 31337, 13)
    b = drv.make_batch(2**31 + 5, 3, 4, 32, 256, 0.1, 31337, 13)
    c = drv.make_batch(2**31 + 5, 4, 4, 32, 256, 0.1, 31337, 13)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({r.tobytes() for r in a["tokens"]}) == 4
    # the chain holds wherever no restart intervenes
    t = np.concatenate([a["tokens"], a["labels"][:, -1:]], 1).astype(np.int64)
    follows = (t[:, :-1] * 31337 + 13) % 256 == t[:, 1:]
    assert follows.mean() > 0.8


# ------------------------------------------------------------ broken paths
def _unchanged_state(orig):
    import jax.numpy as jnp

    def make(*a, **kw):
        def step(params, opt, batch):
            return params, opt, jnp.float32(np.log(256.0)), {}
        return step
    return make


def _half_batch(orig):
    def make(*a, **kw):
        real = orig(*a, **kw)

        def step(params, opt, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return real(params, opt, half)
        return step
    return make


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
@pytest.mark.parametrize("name", TRAINING_CELLS)
def test_broken_step_is_not_correct(name, fault, scratch, monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "make_train_step", fault(loop.make_train_step))
    cell = tiny_cell(name)
    res = driver(cell).run(tiny_ctx(cell, scratch))
    assert not correct(res), res["checks"]


def test_altered_replica_is_not_correct(scratch, monkeypatch):
    """A byte of a replica's copy altered where the copy is produced."""
    from repro.checkpoint.replicate import CheckpointReplicator
    orig = CheckpointReplicator.replicate

    def replicate(self, rel, max_steps=1000):
        ok = orig(self, rel, max_steps)
        d = os.path.join(self.site_dir(self.replicas[-1]), rel)
        leaf = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
        with open(os.path.join(d, leaf), "r+b") as f:
            f.seek(-1, os.SEEK_END)
            last = f.read(1)
            f.seek(-1, os.SEEK_END)
            f.write(bytes([last[0] ^ 0x01]))
        return ok

    monkeypatch.setattr(CheckpointReplicator, "replicate", replicate)
    cell = tiny_cell("ckpt-replicate")
    res = driver(cell).run(tiny_ctx(cell, scratch))
    assert not correct(res), res["checks"]
    assert res["checks"]["leaves_differ_STORE"][0] > 0


def test_control_in_lower_precision_is_not_correct(scratch):
    """The reference in float8 in the program's place, judged by each training
    cell's committed limits through the harness's rule, is not correct on any
    seed, and the program on the same seeds is.  The model has the cells'
    published widths and two layers, at 2 x 256 tokens a step: the widths set
    the program's rounding, and the tests' tiny widths read several times the
    gaps that the cells do.  ``readings.py`` takes the same readings at the
    cells' own sizes on the chip."""
    cell = tiny_cell("train-nosave")
    cell.config.update(Cell.load(ROOT, BENCH, "train-nosave").config,
                       num_hidden_layers=2)
    cell.workload.update(batch=2, seq=256)
    drv = driver(cell)
    opt = cell.config["optimizer"]
    limits = [tiny_cell(name).limits for name in TRAINING_CELLS]
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        ctx = tiny_ctx(cell, scratch, seed=seed)
        prog = {k: v for k, (v, _) in drv.run(ctx)["checks"].items()}
        ref = drv.load_reference(ctx)
        job = drv.Job(ctx, cell.workload, data_seed=drv.derived_seed(seed, 2))
        batches = [job.batch(k) for k in range(drv.N_COMPARED_STEPS)]
        wseed = drv.derived_seed(seed, 1)
        f32 = ref.train_steps(cell.config, opt, wseed, batches)
        again = ref.train_steps(cell.config, opt, wseed, batches)
        assert all(v == 0 for v, _ in
                   drv.compare(again, f32, cell.limits).values())
        fp8 = ref.train_steps(cell.config, opt, wseed, batches, quant="fp8")
        ctrl = {k: v for k, (v, _) in
                drv.compare(fp8, f32, cell.limits).items()}
        for lim in limits:
            assert harness.correct({k: (v, lim[k]) for k, v in prog.items()}), \
                (seed, prog, lim)
            assert not harness.correct({k: (v, lim[k]) for k, v in ctrl.items()}), \
                (seed, ctrl, lim)
