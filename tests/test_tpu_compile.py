"""The device paths compiled for a described TPU v5e (nothing runs).

The TPU compiler refuses here what the chip would refuse: a Pallas
primitive with no TPU lowering, float64 in a compiled kernel, a program
larger than the chip's memory.  The topology is described inside a module
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import load_chip_smoke

# usable HBM of one v5e chip as its compiler reports it (15.75 GiB), taken
# in decimal GB to leave margin
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_checksum_kernel_compiles_at_256_mib(one_chip):
    from repro.kernels.checksum.checksum import checksum_words_pallas
    n = (256 << 20) // 4
    compiled = checksum_words_pallas.lower(
        _sds((n,), jnp.uint32, one_chip), _sds((), jnp.uint32, one_chip),
        _sds((), jnp.uint32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_segment_step_compiles_in_f64(one_chip):
    from repro.ensemble.batch import _lane_segment_jnp
    with jax.enable_x64(True):
        arg = _sds((256, 4608), jnp.float64, one_chip)
        compiled = jax.jit(jax.vmap(_lane_segment_jnp)).lower(
            arg, arg, arg, arg).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= 4 * 256 * 4608 * 8


def test_full_width_train_step_fits_one_chip(one_chip):
    from repro.configs import get_config
    from repro.models.model import LM
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, make_train_step

    smoke = load_chip_smoke()
    cfg = get_config("smollm-135m")
    b, s = smoke.TRAIN_BATCH, smoke.TRAIN_SEQ
    tc = TrainConfig(steps=6, batch_size=b, seq_len=s)
    model = LM(cfg, remat=tc.remat)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw.init, params)
    batch = {k: _sds((b, s), jnp.int32, one_chip) for k in ("tokens", "labels")}
    compiled = make_train_step(model, adamw.AdamWConfig(), tc).lower(
        place(params), place(opt), batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used


def test_moe_mla_train_step_fits_one_chip(one_chip):
    """The moe-mla-train cell's step (DeepSeek-V2-Lite, 5 layers, 8 held of
    64 experts, a 12,800-row vocabulary) at 2 x 4096 tokens with per-layer
    recomputation, as its configuration file and traffic state."""
    import importlib.util
    import json
    import os
    from repro.models.model import LM
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, make_train_step

    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")
    spec = importlib.util.spec_from_file_location(
        "moe_train_job", os.path.join(bench, "drivers", "moe_train_job.py"))
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    with open(os.path.join(bench, "configs", "deepseek-v2-lite-ep8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "workloads", "moe-mla-train.json")) as f:
        t = json.load(f)
    assert cfg["remat"] and (t["batch"], t["seq"]) == (2, 4096)
    model = LM(drv.model_config(cfg), remat=cfg["remat"])
    tc = TrainConfig(steps=cfg["optimizer"]["total_steps"], batch_size=t["batch"],
                     seq_len=t["seq"], remat=cfg["remat"])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == 535_060_992
    opt = jax.eval_shape(adamw.init, params)
    batch = {k: _sds((t["batch"], t["seq"]), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    compiled = make_train_step(model, adamw.AdamWConfig(), tc).lower(
        place(params), place(opt), batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used
