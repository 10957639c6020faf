"""The moe-mla-train cell at a tiny DeepSeek-V2 size on the CPU (d 64, 4
heads, kv_lora 32, qk 16/8, v 16, 8 held of 16 routed, top-2, 2 shared):
its driver comes out correct, and not correct with a piece of the
mathematics left out of the program; the program's shares of the experts add
up to the reference's uncut layer; the reference's YaRN is the program's;
the cell's readers read the program's counters; its FLOP count is a hand
count; and a program that cannot hold a share of the experts is refused
before anything runs."""
import copy
import dataclasses
import json
import math
import sys

import numpy as np
import pytest

from chip_bench_tiny import BENCH, ROOT, Cell, correct, driver, tiny_ctx
from chiplib.harness import Reading, load_module
from chiplib.mla_moe_flops import mla_moe_train_flops_per_token

CELL = "moe-mla-train"
TINY_DEEPSEEK = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    vocab_size=256, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=2,
    published={"num_hidden_layers": 27, "n_routed_experts": 16,
               "vocab_size": 102400})
READERS = ["moe_train_mfu", "moe_dropped_share", "moe_load_max"]
# Limits at the tiny size, from CPU readings of 8 program seeds (largest:
# loss 3.3e-3, gradient 4.9e-3, update 9.9e-3) and 2 float8 controls
# (least: 1.9e-2, 3.7e-2, 1.9e-2).  The tiny widths' bfloat16 rounding reads
# 10-20 times the gradient and update gaps of the cell's own size, whose
# limits are in limits/moe-mla-train.json.
TINY_LIMITS = {"loss_gap": 7e-3, "grad_norm_gap": 1.5e-2,
               "update_norm_gap": 1.5e-2}


def tiny_moe_cell() -> Cell:
    cell = copy.deepcopy(Cell.load(ROOT, BENCH, CELL))
    cell.config.update(copy.deepcopy(TINY_DEEPSEEK))
    cell.workload.update(batch=4, seq=64)
    cell.limits = dict(TINY_LIMITS)
    return cell


def reference():
    return load_module(BENCH / "configs" / "deepseek_v2_train_ref.py",
                       "moe_reference")


def read(name, reading):
    return load_module(BENCH / "metrics" / f"{name}.py", f"moe_{name}").read(reading)


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One run of the tiny cell, as its readers see it."""
    cell = tiny_moe_cell()
    ctx = tiny_ctx(cell, tmp_path_factory.mktemp("moe"))
    res = driver(cell).run(ctx)
    trace = type("Trace", (), {"window_s": res["facts"]["window_s"]})()
    facts = dict(res["facts"], steps=res["facts"]["window_steps"])
    reading = Reading(ctx.spans, trace, facts, ctx.window, cell.config,
                      cell.workload, {"bf16_flops_per_s": 197e12})
    return cell, res, reading


def test_driver_runs_its_cell_at_a_tiny_size(tiny_run):
    cell, res, _ = tiny_run
    assert correct(res), res["checks"]
    assert res["attempted"] > 0 and res["setup_s"] > 0
    assert res["facts"]["compiles_in_window"] == 0
    assert res["facts"]["moe"]["steps"] == res["facts"]["window_steps"]
    flips = res["facts"]["routing_flips"]
    layers = 3 - 1
    assert flips["choices"] == layers * 4 * 64 * 2
    assert flips["flipped"] < 0.05 * flips["choices"]


def test_control_in_lower_precision_is_not_correct(tiny_run):
    """The reference in float8 in the program's place is not correct by the
    tiny limits; the program on the same seed is."""
    cell, res, _ = tiny_run
    drv = driver(cell)
    ref = reference()
    seed = tiny_ctx(cell, ROOT).seed
    job = drv.Job(tiny_ctx(cell, ROOT), cell.workload,
                  data_seed=drv.derived_seed(seed, 2))
    batches = [job.batch(k) for k in range(drv.N_COMPARED_STEPS)]
    wseed, opt = drv.derived_seed(seed, 1), cell.config["optimizer"]
    f32 = ref.train_steps(cell.config, opt, wseed, batches)
    fp8 = ref.train_steps(cell.config, opt, wseed, batches, quant="fp8")
    assert res["facts"]["first_losses"]["reference"] == f32["losses"]
    assert not correct({"checks": drv.compare(fp8, f32, cell.limits)})


def _no_mscale(monkeypatch, drv):
    import repro.models.layers as L
    monkeypatch.setattr(L, "yarn_softmax_scale", lambda s: 1.0)


def _renormalised_topk(monkeypatch, drv):
    plain = drv.model_config

    def renormalised(cfg):
        mc = plain(cfg)
        return mc.with_(moe=dataclasses.replace(mc.moe, router_norm_topk=True))
    monkeypatch.setattr(drv, "model_config", renormalised)


def _no_shared_experts(monkeypatch, drv):
    import jax.numpy as jnp
    import repro.models.moe as MOE
    monkeypatch.setattr(MOE, "mlp", lambda p, x: jnp.zeros_like(x))


@pytest.mark.parametrize("fault", [_no_mscale, _renormalised_topk,
                                   _no_shared_experts])
def test_left_out_mathematics_is_not_correct(fault, tmp_path, monkeypatch):
    cell = tiny_moe_cell()
    drv = driver(cell)
    fault(monkeypatch, drv)
    res = drv.run(tiny_ctx(cell, tmp_path))
    assert not correct(res), res["checks"]


# ------------------------------------------------------------------ shares
def test_the_shares_add_up_to_the_uncut_reference_layer():
    """Every share of 8 of the 16 experts, through the program's expert
    layer, plus the shared experts once, is the reference's layer holding
    all 16 (capacity large enough that nothing drops)."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import mlp
    from repro.models.moe import moe_forward

    cell = tiny_moe_cell()
    cfg = dict(cell.config, capacity_factor=16.0, n_routed_experts=16,
               first_held_expert=0)
    drv = driver(cell)
    ref = reference()
    whole = jax.tree_util.tree_map(lambda a: a[0],
                                   ref.init_params(cfg, 3)["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    want, _, _ = ref.moe(whole, x, cfg, ref.make_einsum("f32"))
    got = mlp(whole["shared"], x)
    for e0 in (0, 8):
        mc = drv.model_config(dict(cfg, n_routed_experts=8, first_held_expert=e0))
        mc = mc.with_(moe=dataclasses.replace(mc.moe, n_shared=0))
        part = {k: whole[k][e0:e0 + 8] for k in ("w_gate", "w_up", "w_down")}
        out, stats = moe_forward(dict(part, router=whole["router"]), mc, x)
        assert int(stats["dropped"]) == 0
        got = got + out
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.allclose(np.asarray(got - out), np.asarray(want), atol=2e-5)
    assert jnp.all(jnp.isfinite(got))


def test_the_references_yarn_is_the_programs():
    from repro.models.layers import yarn_freqs, yarn_softmax_scale
    cfg = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text())
    drv = driver(Cell.load(ROOT, BENCH, CELL))
    ref = reference()
    yarn = drv.model_config(cfg).rope_scaling
    np.testing.assert_allclose(
        np.asarray(yarn_freqs(64, 10000.0, yarn)),
        ref.yarn_inv_freq(64, 10000.0, cfg["rope_scaling"]), rtol=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * yarn_softmax_scale(yarn))


# ----------------------------------------------------------------- readers
def test_each_reader_has_its_entry():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"] if m["name"] in READERS}
    assert sorted(entries) == sorted(READERS)
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
               for m in entries.values())


def test_readers_read_the_programs_counters(tiny_run):
    cell, res, reading = tiny_run
    moe = res["facts"]["moe"]
    assert read("moe_dropped_share", reading) == pytest.approx(
        100.0 * moe["dropped"] / moe["routed"])
    # a load lies between the mean and every assignment of 8 experts x 2 layers
    assert 1.0 < read("moe_load_max", reading) < 8 * 2
    steps, w = reading.facts["steps"], cell.workload
    assert read("moe_train_mfu", reading) == pytest.approx(
        100.0 * mla_moe_train_flops_per_token(cell.config, w["seq"])
        * steps * w["batch"] * w["seq"] / reading.trace.window_s / 197e12)


def _empty_recorder(monkeypatch):
    from repro.obs import spans
    monkeypatch.setattr(spans, "RECORDER", spans.Recorder())


def _no_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    import repro.obs
    monkeypatch.delattr(repro.obs, "spans", raising=False)


@pytest.mark.parametrize("program", [_empty_recorder, _no_recorder])
@pytest.mark.parametrize("name", ["moe_dropped_share", "moe_load_max"])
def test_readers_read_nothing_without_the_programs_spans(name, program,
                                                         tiny_run, monkeypatch):
    program(monkeypatch)
    assert read(name, tiny_run[2]) is None


def test_model_flops_match_a_hand_count_for_deepseek_v2_lite_ep8():
    cfg = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text())
    d, h, layers, vocab = 2048, 16, 5, 12800
    attn = (d * h * 192 + d * (512 + 64) + 512 * h * (128 + 128)
            + h * 128 * d)
    dense = 3 * d * 10944
    expert = 3 * d * 1408
    moe = d * 64 + 2 * expert + expert * 6 * 8 / 64
    params = layers * attn + dense + 4 * moe + vocab * d
    assert params == 257_949_696
    attention = 6 * layers * h * (192 + 128) * 4096
    flops = mla_moe_train_flops_per_token(cfg, 4096)
    assert flops == 6 * params + attention
    assert math.isclose(flops, 2.177e9, rel_tol=1e-3)


# ---------------------------------------------------------------- refusal
def test_a_program_that_holds_every_expert_is_refused_first(tmp_path,
                                                            monkeypatch):
    """A program whose MoEConfig has no held-experts field (the parent of
    this configuration) is refused before the loop starts."""
    import repro.models.config as C
    import repro.train.loop as loop

    @dataclasses.dataclass(frozen=True)
    class Whole:
        n_routed: int = 64

    monkeypatch.setattr(C, "MoEConfig", Whole)
    monkeypatch.setattr(loop, "train", lambda *a, **kw: pytest.fail("ran"))
    cell = tiny_moe_cell()
    with pytest.raises(RuntimeError, match="cannot hold a share"):
        driver(cell).run(tiny_ctx(cell, tmp_path))
