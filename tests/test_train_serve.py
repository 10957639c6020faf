"""Training loop (fault injection, restart, loss decrease) and serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.replicate import CheckpointReplicator
from repro.configs import get_config
from repro.models.model import LM
from repro.serve.engine import Engine
from repro.optim import adamw
from repro.train.loop import TrainConfig, make_train_step, train


def test_train_loss_decreases(tmp_path):
    cfg = get_config("smollm-135m").smoke()
    tc = TrainConfig(steps=40, batch_size=8, seq_len=64, peak_lr=1e-3,
                     warmup=5, ckpt_dir=None, log_every=0)
    res = train(cfg, tc)
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.05, (first, last)


def test_train_restart_resumes_and_completes(tmp_path):
    cfg = get_config("smollm-135m").smoke()
    ckpt = str(tmp_path / "ckpts")
    tc = TrainConfig(steps=24, batch_size=4, seq_len=32, ckpt_every=8,
                     ckpt_dir=ckpt, fail_at_step=13, log_every=0)
    res = train(cfg, tc)
    assert res.restarts == 1
    assert res.final_step == 24
    assert res.restored_from is not None and "step-000008" in res.restored_from
    # checkpoint at final step exists? last save at 24
    assert os.path.isdir(os.path.join(ckpt, "step-000024"))


def test_train_with_replication_protects_against_pod_loss(tmp_path):
    cfg = get_config("smollm-135m").smoke()
    rep = CheckpointReplicator(str(tmp_path), primary="POD0",
                               replicas=("POD1",))
    ckpt = os.path.join(rep.site_dir("POD0"), "ckpts")
    tc = TrainConfig(steps=10, batch_size=4, seq_len=32, ckpt_every=5,
                     ckpt_dir=ckpt, replicator=rep, log_every=0)
    train(cfg, tc)
    pod1 = os.path.join(rep.site_dir("POD1"), "ckpts")
    assert sorted(os.listdir(pod1)) == ["step-000005", "step-000010"]


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen3-14b"])
def test_microbatch_accumulation_matches_one_batch(arch):
    """Two accumulated microbatches give the whole batch's loss and, after
    one step, the same parameters."""
    cfg = get_config(arch).smoke()
    model = LM(cfg, remat=False)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    out = {}
    for mb in (1, 2):
        params = model.init(jax.random.PRNGKey(0))
        tc = TrainConfig(steps=10, batch_size=4, seq_len=32, microbatches=mb)
        step = make_train_step(model, adamw.AdamWConfig(), tc)
        params, _, loss, _ = step(params, adamw.init(params), batch)
        out[mb] = (float(loss), jax.device_get(params))
    assert abs(out[2][0] - out[1][0]) <= 1e-6 * abs(out[1][0]), out
    for a, b in zip(jax.tree_util.tree_leaves(out[1][1]),
                    jax.tree_util.tree_leaves(out[2][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_matches_manual_decode():
    """Wave engine (same-length prompts) must equal manual prefill+decode."""
    cfg = get_config("smollm-135m").smoke()
    model = LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(2)]
    eng = Engine(cfg, params, max_batch=2, max_seq=64)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = sorted(eng.run_to_completion(), key=lambda r: r.rid)

    # manual: batched prefill + greedy decode
    toks = np.stack(prompts)
    cache = model.init_cache(2, 64)
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(toks)}, cache)
    cur = np.asarray(jnp.argmax(logits[:, 0], -1))
    outs = [[int(c)] for c in cur]
    t = 16
    for _ in range(4):
        lg, cache = model.decode_step(params, cache,
                                      jnp.asarray(cur[:, None], jnp.int32),
                                      jnp.int32(t))
        cur = np.asarray(jnp.argmax(lg[:, 0], -1))
        for i, c in enumerate(cur):
            outs[i].append(int(c))
        t += 1
    for r, manual in zip(done, outs):
        assert r.out_tokens == manual


def test_engine_handles_more_requests_than_slots():
    cfg = get_config("smollm-135m").smoke()
    model = LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=2, max_seq=48)
    rng = np.random.default_rng(1)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                       max_new_tokens=3) for _ in range(5)]
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == sorted(rids)
    assert all(len(r.out_tokens) == 3 for r in done)
    assert eng.waves == 3


def test_straggler_requeue(tmp_path):
    """A shard read exceeding the deadline is requeued, training never stalls."""
    from repro.data.sharded import ShardedDataset, write_shards
    root = str(tmp_path / "shards")
    toks = np.arange(2048, dtype=np.int32)
    write_shards(root, toks, shard_len=256)
    ds = ShardedDataset(root, straggler_deadline_s=0.2)
    slow = {"shard-00001.npy"}
    import time

    def hook(name):
        if name in slow:
            slow.discard(name)      # slow exactly once
            time.sleep(0.5)

    ds.load_hook = hook
    it = ds.batches(batch=1, seq=255)
    seen = [next(it)[0]["tokens"][0, 0] for _ in range(8)]
    assert "shard-00001.npy" in ds.slow_shards
    # shard 1 was requeued, not dropped: its first token appears eventually
    assert any(int(s) == 256 for s in seen)
