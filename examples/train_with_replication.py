"""End-to-end driver: training + the paper's replication machinery together.

    PYTHONPATH=src python examples/train_with_replication.py [--steps 60]

What it shows, in one run:
  1. dataset staged from a slow "STORE" site to two pods by the same
     ``CheckpointReplicator`` that guards the checkpoints: the Figure-4
     scheduler over real files (LocalFSTransport + checksums);
  2. training on the pod-local copy with periodic checkpoints;
  3. every committed checkpoint replicated cross-site (POD1 + STORE);
  4. a simulated pod loss (primary checkpoint tree destroyed) and recovery
     from the nearest replica — the paper's reliability story as a training
     framework feature.
"""
import argparse
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.checkpoint.ckpt import save_checkpoint
from repro.checkpoint.replicate import CheckpointReplicator
from repro.configs import get_config
from repro.data.sharded import ShardedDataset, write_shards
from repro.models.model import LM
from repro.optim import adamw
from repro.train.loop import TrainConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    cfg = get_config("smollm-135m").smoke()

    with tempfile.TemporaryDirectory() as td:
        # -- 1. stage the dataset from the slow store to both pods ----------
        staging = CheckpointReplicator(td, primary="STORE",
                                       replicas=("POD0", "POD1"))
        store_ds = os.path.join(staging.site_dir("STORE"), "datasets", "tokens")
        rng = np.random.default_rng(0)
        write_shards(store_ds, rng.integers(0, cfg.vocab_size, 200_000
                                            ).astype(np.int32), 4096)
        ok = staging.replicate("datasets/tokens")
        print(f"[stage] dataset staged to both pods; verified={ok}")

        # -- 2. train from the pod-local copy -------------------------------
        data = ShardedDataset(os.path.join(staging.site_dir("POD0"),
                                           "datasets", "tokens"))
        model = LM(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init(params)
        tc = TrainConfig(steps=args.steps, batch_size=4, seq_len=128)
        step_fn = make_train_step(model, adamw.AdamWConfig(), tc)

        rep = CheckpointReplicator(td, primary="POD0",
                                   replicas=("POD1", "STORE"))
        ckpt_root = os.path.join(rep.site_dir("POD0"), "ckpts")
        it = data.batches(tc.batch_size, tc.seq_len)
        losses = []
        import jax.numpy as jnp
        for step in range(args.steps):
            batch_np, state = next(it)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt, loss, _ = step_fn(params, opt, batch)
            losses.append(float(loss))
            if (step + 1) % 20 == 0:
                d = save_checkpoint(ckpt_root, step + 1,
                                    {"params": params, "opt": opt})
                ok = rep.replicate(os.path.relpath(d, rep.site_dir("POD0")))
                print(f"[train] step {step+1} loss {float(loss):.4f} "
                      f"ckpt replicated={ok}")

        # -- 3. pod loss + recovery from replica -----------------------------
        shutil.rmtree(ckpt_root)
        print("[failure] POD0 checkpoint tree destroyed (simulated pod loss)")
        got = rep.restore_anywhere("ckpts", {"params": params, "opt": opt})
        assert got is not None
        step0, tree, _, site = got
        print(f"[recover] restored step {step0} from {site}; "
              f"loss trace {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
