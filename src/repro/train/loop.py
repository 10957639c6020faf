"""Fault-tolerant training loop.

Wires together: model (any of the 10 archs), AdamW, data pipeline (synthetic
or sharded files), periodic checkpointing with integrity manifests, optional
cross-site checkpoint replication (the paper's scheduler), restart-from-
manifest, and failure injection for tests.

Designed so that a process crash at ANY step resumes bit-compatibly:
  * params/opt state from the last committed checkpoint (verified);
  * data pipeline from its serialized IterState (exact delivery state);
  * step counter from the checkpoint metadata.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.checkpoint.replicate import CheckpointReplicator
from repro.data.synthetic import for_model
from repro.models.config import ModelConfig
from repro.models.model import LM
from repro.obs import spans
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine


@dataclass
class TrainConfig:
    steps: int = 100
    batch_size: int = 8
    seq_len: int = 128
    microbatches: int = 1            # gradient accumulation factor
    peak_lr: float = 3e-4
    warmup: int = 20
    ckpt_every: int = 25
    ckpt_dir: Optional[str] = None
    replicator: Optional[CheckpointReplicator] = None
    seed: int = 0
    log_every: int = 10
    fail_at_step: Optional[int] = None      # fault injection (tests)
    remat: bool = False


@dataclass
class TrainResult:
    losses: List[float]
    final_step: int
    restarts: int
    restored_from: Optional[str] = None
    wall_s: float = 0.0
    # final {"params", "opt"} on the device: the state the last checkpoint
    # (if ``steps`` is a multiple of ``ckpt_every``) saved
    state: Optional[Dict[str, Any]] = None


def make_train_step(model: LM, opt_cfg: adamw.AdamWConfig,
                    train_cfg: TrainConfig):
    """Builds the jitted (params, opt_state, batch) -> ... step with
    microbatch gradient accumulation."""
    mb = train_cfg.microbatches

    def loss_fn(params, batch):
        loss, metrics = model.loss_fn(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, batch):
        if mb == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
            parts = jax.tree_util.tree_map(split, batch)

            def body(carry, mb_batch):
                acc, loss_acc = carry
                (l, _), g = grad_fn(params, mb_batch)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, loss_acc + l), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(body, (zero, 0.0), parts)
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
            loss = loss / mb
            metrics = {}
        lr = warmup_cosine(opt_state.step, train_cfg.peak_lr,
                           train_cfg.warmup, train_cfg.steps)
        params, opt_state, opt_metrics = adamw.update(
            grads, opt_state, lr, opt_cfg)
        if "moe" in metrics:            # an expert model's counters
            opt_metrics = {**opt_metrics, "moe": metrics["moe"]}
        return params, opt_state, loss, opt_metrics

    return jax.jit(step, donate_argnums=(0, 1))


class SimulatedFailure(RuntimeError):
    pass


def train(cfg: ModelConfig, tc: TrainConfig,
          data_iter_factory: Optional[Callable] = None) -> TrainResult:
    """Run training with automatic restart on (injected) failures."""
    t0 = time.time()
    losses: List[float] = []
    restarts = 0
    restored_from = None
    fail_at = tc.fail_at_step

    while True:
        try:
            model = LM(cfg, remat=tc.remat)
            key = jax.random.PRNGKey(tc.seed)
            params = model.init(key)
            opt_state = adamw.init(params)
            start_step = 0

            if tc.ckpt_dir:
                got = restore_checkpoint(
                    tc.ckpt_dir, {"params": params, "opt": opt_state})
                if got is not None:
                    start_step, tree, d = got
                    params, opt_state = tree["params"], tree["opt"]
                    restored_from = d

            data = (data_iter_factory(cfg, tc) if data_iter_factory
                    else for_model(cfg, tc.batch_size, tc.seq_len, tc.seed))
            step_fn = make_train_step(model, adamw.AdamWConfig(), tc)

            for step in range(start_step, tc.steps):
                with spans.span("train.batch", step=step):
                    batch_np = data.batch_at(step)
                with spans.span("train.h2d", step=step) as s:
                    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
                    s.set(bytes=sum(v.nbytes for v in batch_np.values()))
                if fail_at is not None and step == fail_at:
                    fail_at = None   # fail exactly once
                    raise SimulatedFailure(f"injected failure at step {step}")
                with spans.span("train.dispatch", step=step):
                    params, opt_state, loss, out = step_fn(params, opt_state,
                                                           batch)
                losses.append(float(loss))
                if "moe" in out:
                    with spans.span("train.moe", step=step) as s:
                        s.set(**{k: int(v) for k, v in
                                 jax.device_get(out["moe"]).items()})
                if tc.log_every and step % tc.log_every == 0:
                    print(f"[train] step {step} loss {float(loss):.4f}")
                next_step = step + 1
                if tc.ckpt_dir and next_step % tc.ckpt_every == 0:
                    d = save_checkpoint(
                        tc.ckpt_dir, next_step,
                        {"params": params, "opt": opt_state})
                    if tc.replicator is not None:
                        rel = os.path.relpath(
                            d, tc.replicator.site_dir(tc.replicator.primary))
                        tc.replicator.replicate(rel)
            return TrainResult(losses, tc.steps, restarts, restored_from,
                               time.time() - t0,
                               state={"params": params, "opt": opt_state})
        except SimulatedFailure as e:
            print(f"[train] FAILURE: {e}; restarting from checkpoint")
            restarts += 1
            if not tc.ckpt_dir:
                raise
