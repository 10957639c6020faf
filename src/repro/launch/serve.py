"""Serving launcher: load (or init) a model and serve a batch of requests.

    python -m repro.launch.serve --arch falcon-mamba-7b --requests 8
        [--ckpt-dir DIR] [--max-new 16] [--max-batch 4] [--max-seq 256]

Loads the latest verified checkpoint when ``--ckpt-dir`` is given (falling
back to random init), then drives the wave-batched engine.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models.model import LM
from repro.serve.engine import Engine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = LM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        from repro.checkpoint.ckpt import restore_checkpoint
        got = restore_checkpoint(args.ckpt_dir, {"params": params})
        if got is not None:
            step, tree, d = got
            params = tree["params"]
            print(f"loaded checkpoint step {step} from {d}")

    eng = Engine(cfg, params, max_batch=args.max_batch, max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for _ in range(args.requests):
        plen = int(rng.integers(4, args.max_seq // 4))
        if cfg.n_codebooks > 1:
            prompt = rng.integers(0, cfg.vocab_size, (plen, cfg.n_codebooks))
        else:
            prompt = rng.integers(0, cfg.vocab_size, plen)
        eng.submit(prompt, max_new_tokens=args.max_new)
    done = eng.run_to_completion()
    wall = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in {wall:.1f}s "
          f"({eng.waves} waves, {toks/max(wall,1e-9):.1f} tok/s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
