"""Launcher CLIs and sharding rules."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import shardings as SH
from repro.models.model import LM


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])


def test_param_specs_cover_every_leaf():
    """Every param leaf gets a spec; non-divisible axes are dropped."""
    mesh = _mesh11()
    for arch in ("smollm-135m", "deepseek-v2-lite-16b", "falcon-mamba-7b",
                 "zamba2-1.2b", "gemma3-27b"):
        cfg = get_config(arch).smoke()
        model = LM(cfg, remat=False)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = SH.param_specs(shapes, cfg, mesh)
        n_leaves = len(jax.tree_util.tree_leaves(
            shapes, is_leaf=lambda x: hasattr(x, "shape")))
        n_specs = len(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves, arch


def test_logical_rules_divisibility_gate():
    """Head sharding enabled only when KV heads divide the TP axis."""
    class FakeMesh:                           # emulate tp=16 on 1-device CPU
        shape = {"data": 16, "model": 16}
    cfg_div = get_config("gemma3-27b")        # kv=16 -> divisible
    cfg_odd = get_config("qwen3-14b")         # kv=8, H=40 -> not divisible
    rules_div = SH.logical_rules(FakeMesh(), 256, cfg_div)
    rules_odd = SH.logical_rules(FakeMesh(), 256, cfg_odd)
    assert rules_div["heads"] == "model"
    assert rules_odd["heads"] is None         # 8 kv heads % 16 != 0


def test_cache_specs_shard_batch_and_heads():
    mesh = _mesh11()
    cfg = get_config("musicgen-large").smoke()
    model = LM(cfg, remat=False)
    shapes = jax.eval_shape(lambda: model.init_cache(8, 64))
    specs = SH.cache_specs(shapes, 8, 64, mesh, "data")
    for leaf in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)):
        assert isinstance(leaf, P)


def test_train_cli_smoke(tmp_path):
    from repro.launch.train import main
    rc = main(["--arch", "smollm-135m", "--steps", "6", "--batch", "2",
               "--seq", "32", "--ckpt-dir", str(tmp_path / "ck"),
               "--ckpt-every", "3"])
    assert rc == 0


def test_serve_cli_smoke():
    from repro.launch.serve import main
    rc = main(["--arch", "smollm-135m", "--requests", "2", "--max-new", "3",
               "--max-batch", "2", "--max-seq", "64"])
    assert rc == 0


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The persistent cache lives in $JAX_COMPILATION_CACHE_DIR when set
    (left to JAX, nothing else set), else at the fixed <repo>/.jax_cache."""
    import os

    from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
            assert enable_compile_cache() == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = enable_compile_cache()
            assert got == str(REPO_CACHE_DIR) == jax.config.jax_compilation_cache_dir
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
