"""A training job as its user runs it: ``repro.train.loop.train``, unmodified.

The job trains a Llama-architecture model from the seed on a token stream that
the benchmark makes (``make_batch``).  With ``ckpt_every`` in the traffic file,
every save is replicated by ``CheckpointReplicator`` from the primary site to
the replica sites, and after the window the primary's checkpoints are deleted
and the job resumes from the nearest replica.  Without it the job saves
nothing.

The benchmark sees the loop through three seams the loop already has: the
data iterator's ``batch_at`` (called once before each step, after the previous
step's loss reached the host), the replicator passed in ``TrainConfig``, and the
module functions ``make_train_step`` and ``save_checkpoint``, which it wraps for
the length of the run to time them and to read the first steps' state.

The window opens before step ``warm_steps``.  Without saves it closes before
the first step past ``--seconds``; with saves it closes after the first save
and replication to end past ``--seconds``, so it holds the steps up to a save
and that save.

``correct`` compares, against the float32 reference named by the configuration
file: each of the first three steps' loss, the first gradient as AdamW takes it
(from its first moment after one step), and the change of the float32 weights
after three steps, each by its worst leaf.  With saves, it also requires that
every replication verified, that the state restored from each replica site
equals, bit for bit, the state that was saved, and that the resumed step's
loss is finite.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import os
import shutil
import time
from pathlib import Path

import numpy as np

N_COMPARED_STEPS = 3
CKPT_DIR = "ckpts"


class StopWindow(Exception):
    """Raised from a seam of the loop to end the job after the window."""


def derived_seed(seed: int, salt: int) -> int:
    """A 31-bit seed drawn from the run's seed (``PRNGKey`` keeps 32 bits)."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


def make_batch(seed: int, step: int, batch: int, seq: int, vocab: int,
               noise: float, mult: int, add: int) -> dict:
    """Batch ``step`` of a token stream that follows ``x' = (mult * x + add)
    mod vocab`` and restarts at a random token with probability ``noise``:
    learnable, and every row differs.  Vectorised: a token ``k`` places after
    the last restart ``r`` is ``A[k] * r + C[k] mod vocab``."""
    rng = np.random.default_rng([seed, step])
    n = seq + 1
    restart = rng.random((batch, n)) < noise
    restart[:, 0] = True
    rand = rng.integers(0, vocab, (batch, n))
    pos = np.arange(n)
    last = np.maximum.accumulate(np.where(restart, pos, 0), axis=1)
    mul, off = np.ones(n, np.int64), np.zeros(n, np.int64)
    for k in range(1, n):
        mul[k] = mul[k - 1] * mult % vocab
        off[k] = (off[k - 1] * mult + add) % vocab
    k = pos - last
    start = np.take_along_axis(rand, last, axis=1)
    toks = (mul[k] * start + off[k]) % vocab
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def load_reference(ctx):
    path = Path(ctx.config_path).parent / ctx.config["reference"]
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(cfg: dict):
    """The program's model configuration from the configuration file's keys."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], max_seq_len=cfg["max_position_embeddings"])


def worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """Largest ``|prog - ref|`` over the leaves in ``keep``, each against the
    larger of its reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


class Job:
    """State of one run: the window, the wrapped seams, the first steps."""

    def __init__(self, ctx, traffic: dict, data_seed: int):
        self.ctx, self.t = ctx, traffic
        self.saves = bool(traffic.get("ckpt_every"))
        if traffic["warm_steps"] < N_COMPARED_STEPS or (
                self.saves and traffic["ckpt_every"] <= traffic["warm_steps"]):
            raise ValueError("warm_steps must cover the compared steps and "
                             "end before the first save")
        self.data_seed = data_seed
        self.t_w0 = self.t_w1 = None
        self.step_w0 = self.step_w1 = None
        self.last_step = -1
        self.replicated = []
        self.saved_tree = None          # the newest save's tree, not donated
        self.step_fn = None
        self.calls = 0
        self.prog = {"losses": []}
        self._master0 = None
        self._tracing = False

    # ------------------------------------------------------------- the feed
    def batch(self, step: int) -> dict:
        t = self.t
        return make_batch(self.data_seed, step, t["batch"], t["seq"],
                          self.ctx.config["vocab_size"], t["restart_share"],
                          t["chain_mult"], t["chain_add"])

    def batch_at(self, step: int) -> dict:
        now = time.perf_counter()
        if self.t_w0 is None:
            if step >= self.t["warm_steps"]:
                self._open(now, step)
        elif not self.saves:
            if self._tracing and step - self.step_w0 >= self.t["trace_steps"]:
                self._trace_stop()
            if now - self.t_w0 >= self.ctx.seconds:
                self._close(now, step)
        self.last_step = step
        with self.ctx.spans.span("feed"):
            return self.batch(step)

    def _open(self, now: float, step: int) -> None:
        self.t_w0, self.step_w0 = now, step
        self.ctx.window_opened(now)
        if self.ctx.trace:
            self.ctx.trace_start()
            self._tracing = True

    def _close(self, now: float, next_step: int) -> None:
        if self._tracing:
            self._trace_stop()
        self.t_w1, self.step_w1 = now, next_step
        raise StopWindow

    def _trace_stop(self) -> None:
        self.ctx.trace_stop(steps=self.last_step + 1 - self.step_w0)
        self._tracing = False

    # -------------------------------------------------------- wrapped seams
    def wrap_step(self, make_train_step):
        def make(*a, **kw):
            fn = make_train_step(*a, **kw)
            self.step_fn = fn
            return self._first_steps(fn)
        return make

    def _first_steps(self, fn):
        """``fn`` itself, reading its state around the first three calls."""
        import jax
        import jax.numpy as jnp

        norms = jax.jit(lambda tree: [
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)])
        paths = None
        span = self.ctx.spans.span

        def step(params, opt, batch):
            nonlocal paths
            i = self.calls
            self.calls += 1
            if i >= N_COMPARED_STEPS:
                with span("step"):
                    return fn(params, opt, batch)
            if i == 0:
                paths = [jax.tree_util.keystr(k) for k, _ in
                         jax.tree_util.tree_flatten_with_path(opt.master)[0]]
                self._master0 = jax.tree_util.tree_map(jnp.copy, opt.master)
            out = fn(params, opt, batch)
            self.prog["losses"].append(float(out[2]))
            new_opt = out[1]
            if i == 0:
                b1 = self.ctx.config["optimizer"]["b1"]
                self.prog["grad1"] = {p: float(n) / (1 - b1) for p, n in
                                      zip(paths, norms(new_opt.m))}
            if i == N_COMPARED_STEPS - 1:
                diff = jax.tree_util.tree_map(jnp.subtract, new_opt.master,
                                              self._master0)
                self.prog["change"] = {p: float(n) for p, n in
                                       zip(paths, norms(diff))}
                self._master0 = None
            return out

        return step

    def wrap_save(self, save_checkpoint):
        def save(root, step, tree, *a, **kw):
            with self.ctx.spans.span("ckpt_save"):
                out = save_checkpoint(root, step, tree, *a, **kw)
            self.saved_tree = tree
            return out
        return save

    def replicator(self, root: str):
        from repro.checkpoint.replicate import CheckpointReplicator
        job, keep = self, self.t["keep"]

        class TimedReplicator(CheckpointReplicator):
            def replicate(self, ckpt_rel, max_steps=1000):
                with job.ctx.spans.span("replicate"):
                    ok = super().replicate(ckpt_rel, max_steps)
                job.replicated.append(ok)
                for site in self.replicas:       # as the primary's keep does
                    d = os.path.join(self.site_dir(site), CKPT_DIR)
                    for old in sorted(os.listdir(d))[:-keep]:
                        shutil.rmtree(os.path.join(d, old), ignore_errors=True)
                if job.t_w0 is not None:
                    if job._tracing:
                        job._trace_stop()
                    now = time.perf_counter()
                    if now - job.t_w0 >= job.ctx.seconds:
                        job._close(now, job.last_step + 1)
                return ok

        return TimedReplicator(root, primary=self.t["primary"],
                               replicas=tuple(self.t["replicas"]))


# ------------------------------------------------------------------ the run
def run(ctx) -> dict:
    import repro.train.loop as loop

    t, cfg = ctx.workload, ctx.config
    opt = cfg["optimizer"]
    wseed = derived_seed(ctx.seed, 1)
    job = Job(ctx, t, data_seed=derived_seed(ctx.seed, 2))
    work = ctx.work_dir()
    checks = {}
    try:
        rep = job.replicator(work) if job.saves else None
        tc = loop.TrainConfig(
            steps=opt["total_steps"], batch_size=t["batch"], seq_len=t["seq"],
            peak_lr=opt["peak_lr"], warmup=opt["warmup"],
            ckpt_every=t.get("ckpt_every") or opt["total_steps"] + 1,
            ckpt_dir=(os.path.join(rep.site_dir(rep.primary), CKPT_DIR)
                      if rep else None),
            replicator=rep, seed=wseed, log_every=0, remat=False)
        feed = type("Feed", (), {"batch_at": staticmethod(job.batch_at)})()
        saved = (loop.make_train_step, loop.save_checkpoint)
        loop.make_train_step = job.wrap_step(saved[0])
        loop.save_checkpoint = job.wrap_save(saved[1])
        try:
            loop.train(model_config(cfg), tc, data_iter_factory=lambda *_: feed)
        except StopWindow:
            pass
        finally:
            loop.make_train_step, loop.save_checkpoint = saved
        if job.t_w1 is None:
            raise RuntimeError("the job ended before the window closed; "
                               "raise optimizer.total_steps")
        ctx.window_closed(job.t_w1)
        steps = job.step_w1 - job.step_w0
        window_s = job.t_w1 - job.t_w0
        out = {"setup_s": job.t_w0 - ctx.t0, "attempted": steps, "failed": 0,
               "metrics": {"train_tokens_per_s":
                           steps * t["batch"] * t["seq"] / window_s},
               "facts": {"window_s": window_s, "window_steps": steps,
                         "compiles_in_window":
                             ctx.spans.compiles_between(job.t_w0, job.t_w1)}}
        if job.saves:
            out["facts"]["saves_in_window"] = ctx.spans.total(
                "ckpt_save", job.t_w0, job.t_w1)[0]
            t_r = time.perf_counter()
            resume_s, checks = resume(ctx, job, rep)
            out["metrics"]["resume_s"] = resume_s
            out["facts"]["compiles_in_resume"] = ctx.spans.compiles_between(
                t_r, time.perf_counter())
        ctx.read_peak()
        if ctx.trace:
            out["facts"]["step_memory"] = step_memory(job, cfg, t)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    job.saved_tree = job.step_fn = None
    gc.collect()
    t_ref = time.perf_counter()
    checks.update(compare_first_steps(ctx, job, wseed))
    out["facts"]["reference_s"] = time.perf_counter() - t_ref
    out["checks"] = checks
    return out


def resume(ctx, job, rep):
    """Lose the primary, restore from the nearest replica, take one step.

    ``resume_s`` is the restore and the step.  The state as saved is copied to
    the host and freed on the device first, and the comparison of each
    replica's copy with it between the restore and the step is not timed."""
    import jax
    from repro.checkpoint.ckpt import restore_checkpoint

    step = job.step_w1
    saved = jax.device_get(job.saved_tree)
    job.saved_tree = None
    saved_leaves = jax.tree_util.tree_leaves(saved)
    shutil.rmtree(os.path.join(rep.site_dir(rep.primary), CKPT_DIR))
    t0 = time.perf_counter()
    with ctx.spans.span("restore"):
        got = rep.restore_anywhere(CKPT_DIR, saved, step=step)
        if got is not None:
            state = jax.device_put(got[1])
            jax.block_until_ready(state)
    t_restored = time.perf_counter()
    differ = {}
    if got is not None:
        differ[got[3]] = leaves_differ(saved_leaves, got[1])
    for site in rep.replicas:
        if site not in differ:
            one = restore_checkpoint(os.path.join(rep.site_dir(site), CKPT_DIR),
                                     saved, step=step)
            differ[site] = (len(saved_leaves) if one is None
                            else leaves_differ(saved_leaves, one[1]))
    del saved, saved_leaves
    t1 = time.perf_counter()
    loss = math.nan
    if got is not None:
        b = {k: jax.numpy.asarray(v) for k, v in job.batch(step).items()}
        with ctx.spans.span("resume_step"):
            _, _, loss, _ = job.step_fn(state["params"], state["opt"], b)
            loss = float(loss)
    resume_s = (t_restored - t0) + (time.perf_counter() - t1)
    lim = ctx.limits
    checks = {"replications_not_verified":
              (sum(not ok for ok in job.replicated),
               lim["replications_not_verified"]),
              "resumed_loss_not_finite": (int(not math.isfinite(loss)),
                                          lim["resumed_loss_not_finite"])}
    for site in rep.replicas:
        checks[f"leaves_differ_{site}"] = (differ[site],
                                           lim["restored_leaves_differ"])
    return resume_s, checks


def leaves_differ(saved: list, tree) -> int:
    import jax
    got = jax.tree_util.tree_leaves(jax.device_get(tree))
    if len(got) != len(saved):
        return len(saved)
    return sum(a.dtype != b.dtype or a.shape != b.shape
               or a.tobytes() != np.asarray(b).tobytes()
               for a, b in zip(saved, got))


def compare_first_steps(ctx, job, wseed: int) -> dict:
    """The first three steps against the reference, by the worst leaf."""
    ref = load_reference(ctx).train_steps(
        ctx.config, ctx.config["optimizer"], wseed,
        [job.batch(k) for k in range(N_COMPARED_STEPS)])
    return compare(job.prog, ref, ctx.limits)


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Numbers compared, each with its limit.  A leaf whose reference gradient
    is under a thousandth of the median leaf's moves under AdamW by round-off
    alone and is left out of both leaf comparisons."""
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    keep = [k for k in g if g[k] >= 1e-3 * med]
    if len(prog["losses"]) < N_COMPARED_STEPS or "change" not in prog:
        return {"first_steps_missing": (1, 0)}
    return {
        "loss_gap": (max(abs(a - b) for a, b in
                         zip(prog["losses"], ref["losses"])), limits["loss_gap"]),
        "grad_norm_gap": (worst_leaf_gap(prog["grad1"], g, keep),
                          limits["grad_norm_gap"]),
        "update_norm_gap": (worst_leaf_gap(prog["change"], ref["change"], keep),
                            limits["update_norm_gap"]),
    }


def step_memory(job, cfg: dict, t: dict) -> dict:
    """The compiler's memory analysis of the job's step at the cell's shapes,
    to set beside the device's ``peak_bytes_in_use``."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import LM
    from repro.optim import adamw

    params = jax.eval_shape(LM(model_config(cfg), remat=False).init,
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw.init, params)
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    mem = job.step_fn.lower(params, opt, batch).compile().memory_analysis()
    return {k: int(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")}
