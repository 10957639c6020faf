"""Checkpoint/restart, integrity fallback, replication, elastic reshard."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.checkpoint.ckpt import (latest_step, restore_checkpoint,
                                   save_checkpoint)
from repro.checkpoint.replicate import CheckpointReplicator
from repro.core.integrity import Manifest


def tree_example():
    return {
        "w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
        "b": jnp.ones((7,), jnp.bfloat16) * 1.5,
        "step_scale": jnp.float32(3.0),
        "nested": {"m": jnp.zeros((8, 2), jnp.float32)},
    }


def test_roundtrip_exact(tmp_path):
    t = tree_example()
    save_checkpoint(str(tmp_path), 5, t)
    got = restore_checkpoint(str(tmp_path), t)
    assert got is not None
    step, tree, d = got
    assert step == 5
    for a, b in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_keeps_last_k_and_latest_wins(tmp_path):
    t = tree_example()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, t, keep=3)
    steps = sorted(int(n.split("-")[1]) for n in os.listdir(tmp_path))
    assert steps == [3, 4, 5]
    assert latest_step(str(tmp_path)) == 5


def test_corrupt_checkpoint_falls_back(tmp_path):
    t = tree_example()
    save_checkpoint(str(tmp_path), 1, t)
    save_checkpoint(str(tmp_path), 2, t)
    # corrupt the newest
    d2 = os.path.join(tmp_path, "step-000002")
    victim = [f for f in os.listdir(d2) if f.startswith("leaf-")][0]
    with open(os.path.join(d2, victim), "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff\xff")
    got = restore_checkpoint(str(tmp_path), t)
    assert got is not None and got[0] == 1     # fell back to step 1


def test_uncommitted_checkpoint_ignored(tmp_path):
    t = tree_example()
    save_checkpoint(str(tmp_path), 1, t)
    d = save_checkpoint(str(tmp_path), 2, t)
    os.remove(os.path.join(d, "COMMITTED"))    # simulate crash mid-commit
    got = restore_checkpoint(str(tmp_path), t)
    assert got is not None and got[0] == 1


def test_replicator_restores_from_replica_when_primary_lost(tmp_path):
    rep = CheckpointReplicator(str(tmp_path), primary="POD0",
                               replicas=("POD1", "STORE"))
    t = tree_example()
    ckpt_root = os.path.join(rep.site_dir("POD0"), "ckpts")
    d = save_checkpoint(ckpt_root, 7, t)
    rel = os.path.relpath(d, rep.site_dir("POD0"))
    assert rep.replicate(rel)
    # destroy the primary copy entirely (pod loss)
    shutil.rmtree(ckpt_root)
    got = rep.restore_anywhere("ckpts", t)
    assert got is not None
    step, tree, _, site = got
    assert step == 7 and site in ("POD1", "STORE")
    np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(t["w"]))


def test_replicator_stages_a_dataset_from_the_store(tmp_path):
    """A dataset directory replicates like a checkpoint: primary STORE, the
    pods as replicas; each copy verifies and feeds the same batches."""
    from repro.data.sharded import ShardedDataset, write_shards
    rep = CheckpointReplicator(str(tmp_path), primary="STORE",
                               replicas=("POD0", "POD1"))
    src = os.path.join(rep.site_dir("STORE"), "datasets", "tokens")
    tokens = np.random.default_rng(0).integers(0, 1000, 40_000
                                               ).astype(np.int32)
    assert write_shards(src, tokens, 4096) == 9
    assert rep.replicate("datasets/tokens")
    manifest = Manifest.scan(src)
    assert len(manifest.entries) == 10
    for pod in ("POD0", "POD1"):
        copy = os.path.join(rep.site_dir(pod), "datasets", "tokens")
        assert manifest.verify(copy) == {}
    want, _ = next(ShardedDataset(src).batches(2, 64))
    got, _ = next(ShardedDataset(os.path.join(
        rep.site_dir("POD0"), "datasets", "tokens")).batches(2, 64))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def _over_one_write_slice():
    """float32 rows of 7: more bytes than one write slice, not a multiple."""
    rows = 2 * ckpt._WRITE_BYTES // 28 + 1
    return np.arange(rows * 7, dtype=np.float32).reshape(rows, 7)


def _device_layout_stack():
    return np.arange(6 * 3 * 8, dtype=np.float32).reshape(6, 3, 8
                                                          ).transpose(0, 2, 1)


WRITER_CASES = {
    "bf16-as-uint16": lambda: np.asarray(
        jnp.linspace(-3, 3, 35, dtype=jnp.bfloat16).reshape(5, 7)
    ).view(np.uint16),
    "float32": lambda: np.linspace(-1, 1, 24, dtype=np.float32).reshape(4, 6),
    "int32": lambda: np.arange(-10, 14, dtype=np.int32).reshape(6, 4),
    "scalar-0d": lambda: np.asarray(np.float32(3.25)),
    "1d": lambda: np.arange(11, dtype=np.float32) / 7,
    "3d-stack-slice": lambda: np.arange(6 * 3 * 5, dtype=np.float32
                                        ).reshape(6, 3, 5)[2:4],
    "over-one-write-slice": _over_one_write_slice,
    "fortran-order": lambda: np.asfortranarray(
        np.arange(6 * 10, dtype=np.float32).reshape(6, 10)),
    "strided-view": lambda: np.arange(6 * 20, dtype=np.int32
                                      ).reshape(6, 20)[:, ::3],
    # a stacked leaf as a TPU's device_get returns it (last two axes in
    # Fortran order): a one-layer chunk is Fortran-contiguous, a two-layer
    # chunk is not contiguous at all
    "device-layout-1-layer": lambda: _device_layout_stack()[0:1],
    "device-layout-2-layers": lambda: _device_layout_stack()[1:3],
}


@pytest.mark.parametrize("case", sorted(WRITER_CASES))
def test_write_npy_bytes_equal_np_save(tmp_path, case):
    arr = WRITER_CASES[case]()
    if case == "over-one-write-slice":
        assert arr.nbytes > ckpt._WRITE_BYTES
        assert arr.nbytes % ckpt._WRITE_BYTES
    with open(tmp_path / "ours.npy", "wb") as f:
        ckpt._write_npy(f, arr)
    with open(tmp_path / "np_save.npy", "wb") as f:
        np.save(f, arr)
    assert (tmp_path / "ours.npy").read_bytes() == \
        (tmp_path / "np_save.npy").read_bytes()
    back = np.load(tmp_path / "ours.npy")
    assert back.dtype == arr.dtype and back.shape == arr.shape
    np.testing.assert_array_equal(back, arr)


def every_leaf_kind():
    return {
        "bf16": jnp.linspace(-2, 2, 40, dtype=jnp.bfloat16).reshape(8, 5),
        "f32": jnp.linspace(-1, 1, 24, dtype=jnp.float32).reshape(4, 6),
        "i32": jnp.arange(-6, 6, dtype=jnp.int32).reshape(3, 4),
        "scalar": jnp.float32(3.25),
        "vec": jnp.arange(11, dtype=jnp.float32) / 7,
        "stack": jnp.arange(6 * 3 * 5, dtype=jnp.float32).reshape(6, 3, 5),
        "big": jnp.asarray(_over_one_write_slice()),
        "fortran": np.asfortranarray(np.arange(4 * 9, dtype=np.float32
                                               ).reshape(4, 9)),
        "device_layout": _device_layout_stack(),
    }


def test_saved_files_equal_np_save_and_replicas_restore_bit_equal(
        tmp_path, monkeypatch):
    t = every_leaf_kind()
    rep = CheckpointReplicator(str(tmp_path / "sites"), primary="POD0",
                               replicas=("POD1", "STORE"))
    ckpt_root = os.path.join(rep.site_dir("POD0"), "ckpts")
    d = save_checkpoint(ckpt_root, 3, t)
    with monkeypatch.context() as m:
        m.setattr(ckpt, "_write_npy", lambda f, arr: np.save(f, arr))
        d_ref = save_checkpoint(str(tmp_path / "np_save"), 3, t)
    assert Manifest.scan(d).entries == Manifest.scan(d_ref).entries

    assert rep.replicate(os.path.relpath(d, rep.site_dir("POD0")))
    shutil.rmtree(ckpt_root)
    got = rep.restore_anywhere("ckpts", t)
    assert got is not None
    step, tree, _, site = got
    assert step == 3 and site in ("POD1", "STORE")
    for name, leaf in t.items():
        back = tree[name]
        assert back.dtype == leaf.dtype and back.shape == leaf.shape, name
        assert np.asarray(back).tobytes() == np.asarray(leaf).tobytes(), name


def test_manifest_digests_equal_the_oracle(tmp_path):
    """A saved checkpoint's ``MANIFEST.json`` holds, for every file, its size
    and ``checksum_bytes_np`` of its bytes, files of several fold blocks
    included: manifests written by any version of the hash are
    interchangeable."""
    from repro.core.integrity import _FOLD_WORDS
    from repro.kernels.checksum.ref import checksum_bytes_np
    t = every_leaf_kind()
    t["blocks"] = np.arange(2 * (3 * _FOLD_WORDS + 1),
                            dtype=np.uint32).reshape(2, -1)
    d = save_checkpoint(str(tmp_path), 7, t)
    entries = Manifest.load(os.path.join(d, "MANIFEST.json")).entries
    assert max(size for size, _ in entries.values()) > 12 * _FOLD_WORDS
    assert set(entries) == set(os.listdir(d)) - {"MANIFEST.json",
                                                 "COMMITTED"}
    for rel, (size, csum) in entries.items():
        data = (tmp_path / os.path.basename(d) / rel).read_bytes()
        assert (size, csum) == (len(data), checksum_bytes_np(data)), rel


def test_elastic_reshard_plan():
    from repro.checkpoint.elastic import plan_reshard
    from jax.sharding import PartitionSpec as P
    tree = {"w": np.zeros((64, 64), np.float32)}
    specs = {"w": P("data", "model")}
    plan = plan_reshard(tree, {"data": 4, "model": 4},
                        {"data": 8, "model": 4}, specs)
    assert plan["total_bytes"] == 64 * 64 * 4
    assert plan["approx_bytes_moved_per_device"] > 0
