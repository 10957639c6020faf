#!/usr/bin/env python3
"""Smoke run of the replication system's device paths on a TPU.

    python chip_smoke.py [--seed N]          # one chip: train, verify, ensemble
    python chip_smoke.py --four-chips        # four chips: the relay phase only

Every phase runs in this one process and starts no child: a chip belongs to
one process at a time.

* ``train``: smollm-135m at its full published width trains through
  ``repro.train.loop.train``, checkpointing every 3 steps; each checkpoint is
  replicated by ``CheckpointReplicator`` to two sites.  The primary tree is
  then deleted, ``restore_anywhere`` restores the last step from a replica
  bit-equal to the state that was saved, and one more step runs from it.
* ``verify``: the Pallas integrity kernel, compiled, hashes a 1 GiB buffer
  generated on the device and a length off the kernel's block grid; both
  hashes equal the numpy reference on a host copy.
* ``ensemble``: ``ensemble-paper-bands`` on the lanes engine with the ``jax``
  (device) backend, lane for lane against the numpy backend.
* ``relay`` (``--four-chips`` only): the chain relay, the naive fan-out and
  the ring all-gather of ``core/relay_collectives.py`` under ``jax.shard_map``
  on a four-chip mesh.

Each phase prints one JSON line with its shapes, the device's peak bytes in
use so far and its set-up seconds (compilation included; these are not speed
figures).  The last line is ``{"ok": true, "device": {...}}``.  The script
exits non-zero without that line when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint.replicate import CheckpointReplicator  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import for_model  # noqa: E402
from repro.ensemble.batch import jax_segment_device, numpy_segment_fn  # noqa: E402
from repro.ensemble.engine import run_ensemble  # noqa: E402
from repro.kernels.checksum.checksum import BLOCK_ROWS, checksum_words_pallas  # noqa: E402
from repro.kernels.checksum.ops import checksum_array  # noqa: E402
from repro.kernels.checksum.ref import ROW, checksum_words_np  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.scenarios.registry import get_scenario  # noqa: E402
from repro.train.loop import TrainConfig, make_train_step, train  # noqa: E402

# Shapes of the one-chip run.  train: 8 x 512 tokens per step is the largest
# full-width smollm-135m step that compiles with margin on a 16 GB v5e
# (8 x 1024 needs 24.5 GB, 16 x 512 18.2 GB).  verify: 2**28 words = 1 GiB.
# ensemble: the registered 256 lanes.  Host time grows with lanes x rows x
# iterations, and the iterations with the catalog; the byte scale barely
# moves them (the full catalog takes ~8k ticks even at scale 0.001), so the
# catalog is cut to what two runs (device and reference) finish in minutes.
# Scale 0.1 is the largest byte scale at which every lane ends in numpy's
# replica state on a TPU v5e: at 1.0 (8.2 PB per site, near 2**53) XLA's
# emulated float64 left one lane of 256 one byte short.
TRAIN_BATCH, TRAIN_SEQ = 8, 512
VERIFY_WORDS = 1 << 28
VERIFY_TAIL_WORDS = VERIFY_WORDS - 3 * (BLOCK_ROWS * ROW) // 2 - 7
ENSEMBLE_LANES, ENSEMBLE_DATASETS, ENSEMBLE_SCALE = 256, 256, 0.1
RELAY_BYTES = 256 << 20


class SmokeFailure(AssertionError):
    pass


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _require_on(platform: str, tree, what: str) -> None:
    """Every array leaf of ``tree`` lives on ``platform`` devices."""
    for leaf in jax.tree_util.tree_leaves(tree):
        plats = {d.platform for d in leaf.devices()}
        _require(plats == {platform}, f"{what} is on {plats}, not {platform}")


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class _CheckedReplicator(CheckpointReplicator):
    """Records what every ``replicate`` call returned."""

    def __post_init__(self):
        super().__post_init__()
        self.results = []

    def replicate(self, ckpt_rel: str, max_steps: int = 1000) -> bool:
        ok = super().replicate(ckpt_rel, max_steps)
        self.results.append((ckpt_rel, ok))
        return ok


# ----------------------------------------------------------------- phases
def phase_train(cfg, *, batch: int, seq: int, workdir: str, steps: int = 6,
                ckpt_every: int = 3, seed: int = 0,
                platform: str = "tpu") -> dict:
    """Train, checkpoint and replicate; lose the primary; resume from a
    replica and take one more step."""
    t0 = time.perf_counter()
    rep = _CheckedReplicator(workdir, primary="POD0",
                             replicas=("POD1", "STORE"))
    ckpt_dir = os.path.join(rep.site_dir("POD0"), "ckpts")
    tc = TrainConfig(steps=steps, batch_size=batch, seq_len=seq,
                     ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
                     replicator=rep, seed=seed, log_every=0)
    res = train(cfg, tc)
    _require(len(res.losses) == steps and np.all(np.isfinite(res.losses)),
             f"training losses {res.losses}")
    _require(len(rep.results) == steps // ckpt_every
             and all(ok for _, ok in rep.results),
             f"replicate calls returned {rep.results}")
    _require_on(platform, res.state, "trained state")
    saved = jax.device_get(res.state)       # what the step-`steps` save wrote
    example = res.state
    res.state = None

    shutil.rmtree(ckpt_dir)                 # the primary pod is lost
    got = rep.restore_anywhere("ckpts", example, step=steps)
    _require(got is not None, "no replica restored")
    step, tree, _, site = got
    del example
    _require(step == steps and site != "POD0",
             f"restored step {step} from {site}")
    pairs = zip(jax.tree_util.tree_leaves(saved),
                jax.tree_util.tree_leaves(tree))
    for i, (a, b) in enumerate(pairs):
        a, b = np.asarray(a), np.asarray(b)
        _require(a.dtype == b.dtype and a.shape == b.shape
                 and a.tobytes() == b.tobytes(),
                 f"restored leaf {i} differs from the saved state")
    del saved

    state = jax.device_put(tree)
    _require_on(platform, state, "restored state")
    step_fn = make_train_step(LM(cfg, remat=tc.remat), adamw.AdamWConfig(), tc)
    data = for_model(cfg, batch, seq, seed).batch_at(steps)
    params, opt, loss, _ = step_fn(state["params"], state["opt"],
                                   {k: jnp.asarray(v) for k, v in data.items()})
    _require_on(platform, (params, loss), "resumed step")
    resume_loss = float(loss)
    _require(np.isfinite(resume_loss), f"resumed loss {resume_loss}")
    return {"arch": cfg.name, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "batch": batch, "seq": seq,
            "steps": steps, "ckpt_every": ckpt_every,
            "losses": [float(x) for x in res.losses],
            "replicated": [rel for rel, _ in rep.results],
            "restored_from": site, "resume_loss": resume_loss,
            "peak_bytes_in_use": _peak_bytes(),
            "setup_s": time.perf_counter() - t0}


def phase_verify(*, n_words: int, tail_words: int, seed: int = 0,
                 platform: str = "tpu", interpret: bool = False) -> dict:
    """Hash a device-generated buffer and an off-grid prefix of it with the
    Pallas kernel; both must equal the numpy reference."""
    t0 = time.perf_counter()
    gran = BLOCK_ROWS * ROW
    _require(n_words % gran == 0 and tail_words % gran != 0
             and 0 < tail_words < n_words, "verify shapes")
    words = jax.jit(lambda k: jax.random.bits(k, (n_words,), jnp.uint32))(
        jax.random.PRNGKey(seed))
    h = checksum_words_pallas(words, jnp.uint32(n_words),
                              jnp.uint32((4 * n_words) & 0xFFFFFFFF),
                              interpret=interpret)
    # the public op zero-pads an off-grid length up to the block grid; the
    # kernel's tail mask must drop the pad
    h_tail = checksum_array(words[:tail_words], interpret=interpret)
    _require_on(platform, (words, h, h_tail), "verify buffers and hashes")
    host = np.asarray(words)
    ref = checksum_words_np(host, 4 * n_words)
    ref_tail = checksum_words_np(host[:tail_words], 4 * tail_words)
    _require(int(h) == ref, f"kernel hash {int(h):#010x} != {ref:#010x}")
    _require(int(h_tail) == ref_tail,
             f"tail hash {int(h_tail):#010x} != {ref_tail:#010x}")
    return {"bytes": 4 * n_words, "tail_bytes": 4 * tail_words,
            "block_words": gran, "hash": f"{ref:#010x}",
            "tail_hash": f"{ref_tail:#010x}",
            "peak_bytes_in_use": _peak_bytes(),
            "setup_s": time.perf_counter() - t0}


def phase_ensemble(*, lanes: int, n_datasets: int, scale: float,
                   seed: int = 0, platform: str = "tpu") -> dict:
    """The lanes engine on the jax backend against the numpy reference."""
    t0 = time.perf_counter()
    # one segment step at the ensemble's [lane, (dataset, destination)]
    # shape, on the device, against the numpy step
    rng = np.random.default_rng(seed)
    shape = (lanes, 2 * n_datasets)
    t = rng.uniform(0.0, 3600.0, size=shape)
    bd = rng.uniform(0.0, 1e12, size=shape)
    rate = np.where(rng.random(shape) < 0.2, 0.0,
                    rng.uniform(1e6, 1e9, size=shape))
    bound = bd + rng.uniform(0.0, 1e11, size=shape)
    with jax.enable_x64(True):
        out = jax_segment_device(t, bd, rate, bound)
        _require_on(platform, out, "jax segment step")
        got = [np.asarray(o) for o in out]
    ref_out = numpy_segment_fn(t, bd, rate, bound)
    # XLA emulates float64 on a TPU: report how far each output lands from
    # numpy; the lane-level comparison below is the pass criterion
    step_rel_err = {}
    for g, r, name in zip(got[:4], ref_out[:4],
                          ("t_left", "new_bytes", "adv", "moved")):
        _require(np.array_equal(np.isfinite(g), np.isfinite(r)),
                 f"device segment step {name}: non-finite where numpy is not")
        fin = np.isfinite(r)
        err = np.abs(g[fin] - r[fin]) / np.maximum(np.abs(r[fin]), 1e-300)
        step_rel_err[name] = float(err.max()) if err.size else 0.0
    step_hit_mismatch = int(np.count_nonzero(got[4] != ref_out[4]))

    espec = dataclasses.replace(get_scenario("ensemble-paper-bands"),
                                n_lanes=lanes)
    t1 = time.perf_counter()
    dev = run_ensemble(espec, scale=scale, n_datasets=n_datasets,
                       backend="jax")
    dev_s = time.perf_counter() - t1
    _require(dev.engine == "lanes" and dev.backend == "jax",
             f"ran {dev.engine}/{dev.backend}, not lanes/jax")
    ref = run_ensemble(espec, scale=scale, n_datasets=n_datasets,
                       backend="numpy")
    for i in range(lanes):
        a, b = dev.lane(i), ref.lane(i)
        _require(not a.timed_out, f"lane {i} timed out")
        _require(a.bytes_at == b.bytes_at and a.quarantined == b.quarantined,
                 f"lane {i} ends in another replica state than numpy")
    days = [abs(dev.lane(i).sim_days - ref.lane(i).sim_days)
            for i in range(lanes)]
    iters = [abs(dev.lane(i).iterations - ref.lane(i).iterations)
             for i in range(lanes)]
    same = sum(dev.lane(i).succeeded_digest == ref.lane(i).succeeded_digest
               for i in range(lanes))
    return {"ensemble": espec.name, "lanes": lanes, "datasets": n_datasets,
            "scale": scale, "engine": dev.engine, "backend": dev.backend,
            "max_sim_days_drift": max(days), "max_iteration_drift": max(iters),
            "lanes_with_equal_digest": same,
            "step_max_rel_err": step_rel_err,
            "step_hit_mismatch": step_hit_mismatch,
            "p50_sim_days": dev.bands["sim_days"]["p50"],
            "peak_bytes_in_use": _peak_bytes(),
            "jax_run_setup_s": dev_s,
            "setup_s": time.perf_counter() - t0}


def phase_relay(*, nbytes: int, n_devices: int = 4, seed: int = 0,
                platform: str = "tpu") -> dict:
    """Broadcast one slice to every device of a ``pod`` mesh (chain relay,
    naive fan-out) and gather all slices everywhere (ring all-gather)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.relay_collectives import (naive_broadcast_inner,
                                              relay_broadcast_inner,
                                              ring_all_gather_inner)
    t0 = time.perf_counter()
    devs = jax.devices()[:n_devices]
    _require(len(devs) == n_devices, f"need {n_devices} devices")
    mesh = Mesh(np.array(devs), ("pod",))
    sharded = NamedSharding(mesh, P("pod"))

    def run(inner, words_per_slice):
        gen = jax.jit(lambda k: jax.random.bits(
            k, (n_devices * words_per_slice,), jnp.uint32),
            out_shardings=sharded)
        x = gen(jax.random.PRNGKey(seed))
        fn = jax.jit(jax.shard_map(
            functools.partial(inner, axis_name="pod", axis_size=n_devices),
            mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
            check_vma=False))
        out = fn(x)
        _require_on(platform, (x, out), f"{inner.__name__} buffers")
        shards = out.addressable_shards
        _require(len({s.device for s in shards}) == n_devices,
                 f"{inner.__name__} output is not on {n_devices} devices")
        return np.asarray(x), shards

    words = nbytes // 4
    report = {"bytes": nbytes, "devices": n_devices}
    for inner in (relay_broadcast_inner, naive_broadcast_inner):
        t1 = time.perf_counter()
        x, shards = run(inner, words)
        src = x[:words]                      # slice 0 is the source
        for s in shards:
            _require(np.array_equal(np.asarray(s.data), src),
                     f"{inner.__name__}: {s.device} lacks the source bytes")
        report[f"{inner.__name__}_setup_s"] = time.perf_counter() - t1
    t1 = time.perf_counter()
    x, shards = run(ring_all_gather_inner, words // n_devices)
    for s in shards:
        _require(np.array_equal(np.asarray(s.data), x),
                 f"ring_all_gather_inner: {s.device} lacks the gathered bytes")
    report["ring_all_gather_inner_setup_s"] = time.perf_counter() - t1
    report.update(peak_bytes_in_use=_peak_bytes(),
                  setup_s=time.perf_counter() - t0)
    return report


# ------------------------------------------------------------------- main
def _emit(phase: str, info: dict) -> None:
    print(json.dumps({"phase": phase, **info}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the relay phase, on a four-chip mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: need {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    if args.four_chips:
        _emit("plan", {"relay_bytes": RELAY_BYTES, "device": device})
        _emit("relay", phase_relay(nbytes=RELAY_BYTES, seed=args.seed))
    else:
        _emit("plan", {"train": {"arch": "smollm-135m", "batch": TRAIN_BATCH,
                                 "seq": TRAIN_SEQ},
                       "verify_bytes": 4 * VERIFY_WORDS,
                       "ensemble": {"lanes": ENSEMBLE_LANES,
                                    "datasets": ENSEMBLE_DATASETS,
                                    "scale": ENSEMBLE_SCALE},
                       "device": device})
        workdir = tempfile.mkdtemp(prefix=".chip_smoke-", dir=ROOT)
        try:
            _emit("train", phase_train(get_config("smollm-135m"),
                                       batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                                       workdir=workdir, seed=args.seed))
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        _emit("verify", phase_verify(n_words=VERIFY_WORDS,
                                     tail_words=VERIFY_TAIL_WORDS,
                                     seed=args.seed, interpret=False))
        _emit("ensemble", phase_ensemble(lanes=ENSEMBLE_LANES,
                                         n_datasets=ENSEMBLE_DATASETS,
                                         scale=ENSEMBLE_SCALE,
                                         seed=args.seed))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
