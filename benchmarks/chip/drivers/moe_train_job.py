"""DeepSeek-V2-Lite's training job as its user runs it, on one chip's share
of an expert-parallel deployment: ``repro.train.loop.train``, unmodified.

The job is ``train_job.py``'s without saves: the same ``Job`` (feed, window,
wrapped ``make_train_step``), ``make_batch`` and ``compare``, taken from that
file and re-exported here so that ``readings.py`` takes this driver as it
takes that one.  What is its own:

* ``model_config`` maps the configuration's latent-attention, expert and
  YaRN keys onto the program's ``ModelConfig``; the router keeps the
  published expert count, and the layer holds ``n_routed_experts`` of them
  from ``first_held_expert``;
* ``run`` trains with ``remat`` as the configuration states;
* the window's steps' ``train.moe`` spans (the program's counters of the
  held experts' assignments) are summed into ``facts``;
* ``routing_flips`` in ``facts``: of the first step's top-k choices, how
  many the program's own layers (one forward pass at the initial weights)
  make otherwise than the float32 reference, and ``first_losses``, both
  sides' losses of the compared steps.  Facts, not checks.

A program whose ``MoEConfig`` cannot hold a share of the experts is refused
before anything compiles.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import time
from pathlib import Path

import numpy as np


def _train_job():
    path = Path(__file__).with_name("train_job.py")
    spec = importlib.util.spec_from_file_location("bench_driver_train_job", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_tj = _train_job()
Job, StopWindow, make_batch, derived_seed = (
    _tj.Job, _tj.StopWindow, _tj.make_batch, _tj.derived_seed)
load_reference, compare, N_COMPARED_STEPS = (
    _tj.load_reference, _tj.compare, _tj.N_COMPARED_STEPS)

# published settings the program has one way of computing
FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "moe_layer_freq": 1, "routed_scaling_factor": 1,
         "q_lora_rank": None, "attention_bias": False, "hidden_act": "silu"}


def model_config(cfg: dict):
    """The program's model configuration from the configuration file's keys."""
    from repro.models.config import MLAConfig, ModelConfig, MoEConfig
    if "n_held" not in {f.name for f in dataclasses.fields(MoEConfig)}:
        raise RuntimeError("the program's MoEConfig cannot hold a share of "
                           "the routed experts; nothing was run")
    from repro.models.config import YarnScaling
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: the program computes only "
                             f"{value!r}")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs['type']!r}: only yarn")
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        rope_scaling=YarnScaling(
            factor=rs["factor"],
            original_max_position_embeddings=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(
            n_routed=cfg["published"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"], n_shared=cfg["n_shared_experts"],
            d_ff_expert=cfg["moe_intermediate_size"],
            capacity_factor=cfg["capacity_factor"],
            first_dense_layers=cfg["first_k_dense_replace"],
            d_ff_dense=cfg["intermediate_size"],
            router_norm_topk=cfg["norm_topk_prob"],
            n_held=cfg["n_routed_experts"],
            first_held=cfg["first_held_expert"],
            seq_aux=cfg["seq_aux"], aux_weight=cfg["aux_loss_alpha"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"])


# ------------------------------------------------------------------ the run
def run(ctx) -> dict:
    mc = model_config(ctx.config)          # refuses an unfit program first
    import repro.train.loop as loop

    t, cfg = ctx.workload, ctx.config
    if t.get("ckpt_every"):
        raise ValueError("this job saves nothing: drop ckpt_every")
    opt = cfg["optimizer"]
    wseed = derived_seed(ctx.seed, 1)
    job = Job(ctx, t, data_seed=derived_seed(ctx.seed, 2))
    tc = loop.TrainConfig(
        steps=opt["total_steps"], batch_size=t["batch"], seq_len=t["seq"],
        peak_lr=opt["peak_lr"], warmup=opt["warmup"], seed=wseed,
        log_every=0, remat=cfg["remat"])
    feed = type("Feed", (), {"batch_at": staticmethod(job.batch_at)})()
    saved = loop.make_train_step
    loop.make_train_step = job.wrap_step(saved)
    try:
        loop.train(mc, tc, data_iter_factory=lambda *_: feed)
    except StopWindow:
        pass
    finally:
        loop.make_train_step = saved
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
                         ctx.spans.compiles_between(job.t_w0, job.t_w1),
                     "moe": window_counters(job.t_w0, job.t_w1)}}
    ctx.read_peak()
    if ctx.trace:
        out["facts"]["step_memory"] = step_memory(job, mc, t)
    job.step_fn = None
    gc.collect()
    t_ref = time.perf_counter()
    first = [job.batch(k) for k in range(N_COMPARED_STEPS)]
    prog_routes = program_routes(mc, wseed, first[0])
    ref = load_reference(ctx).train_steps(cfg, opt, wseed, first)
    out["facts"]["routing_flips"] = flips(prog_routes, ref["routes1"])
    out["facts"]["first_losses"] = {"program": job.prog["losses"],
                                    "reference": ref["losses"]}
    out["facts"]["reference_s"] = time.perf_counter() - t_ref
    out["checks"] = compare(job.prog, ref, ctx.limits)
    return out


def window_counters(t0: float, t1: float) -> dict:
    """The program's ``train.moe`` counters over the window's steps: summed
    assignments and drops, and the largest load of any step."""
    from repro.obs import spans
    got = [s.attrs for s in spans.records()
           if s.name == "train.moe" and t0 <= s.start <= t1]
    return {"steps": len(got),
            "routed": sum(a["routed"] for a in got),
            "dropped": sum(a["dropped"] for a in got),
            "load_max": max((a["load_max"] for a in got), default=None)}


def program_routes(mc, seed: int, batch: dict) -> np.ndarray:
    """Each expert layer's top-k choices of ``batch`` by the program's own
    layers at the initial weights the loop draws from ``seed``:
    (layers, tokens, k).  A forward pass of its own, compiled apart from the
    step, so its roundings can differ from the step's now and then."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import moe as MOE
    from repro.models.model import LM, attn_block

    model = LM(mc, remat=False)
    params = model.init(jax.random.PRNGKey(seed))

    @jax.jit
    def routes(params, tokens):
        b, t = tokens.shape
        x = model.embed(params, {"tokens": tokens})
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        for lp in params["lead"]:
            x, _, _ = attn_block(lp, mc, x, pos)

        def layer(x, bp):
            a, _ = L.mla_attention(bp["attn"], mc,
                                   L.rmsnorm(bp["ln1"], x, mc.norm_eps), pos)
            h = L.rmsnorm(bp["ln2"], x + a, mc.norm_eps)
            _, top_i, _ = MOE._routing(bp["moe"], mc.moe,
                                       h.reshape(b * t, -1), b)
            y, _, _ = attn_block(bp, mc, x, pos, use_moe=True)
            return y, top_i

        return jax.lax.scan(layer, x, params["blocks"])[1]

    got = np.asarray(routes(params, jnp.asarray(batch["tokens"])))
    del params
    return got


def flips(prog: np.ndarray, ref: np.ndarray) -> dict:
    """Top-k choices (layer, token, slot) of the program that the reference
    did not make for that token in that layer, of all the choices."""
    same = (prog[..., :, None] == ref[..., None, :]).any(-1)
    return {"flipped": int(same.size - same.sum()), "choices": int(same.size)}


def step_memory(job, mc, t: dict) -> dict:
    """The compiler's memory analysis of the job's step at the cell's shapes,
    to set beside the device's ``peak_bytes_in_use``."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import LM
    from repro.optim import adamw

    params = jax.eval_shape(LM(mc).init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw.init, params)
    batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    mem = job.step_fn.lower(params, opt, batch).compile().memory_analysis()
    return {k: int(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")}
