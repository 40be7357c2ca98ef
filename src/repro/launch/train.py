"""Training launcher.

Two modes:
  * real run (CPU/TPU): train the canonicalizer model on NL->signature pairs
    (the end-to-end driver; examples/train_canonicalizer.py wraps this),
  * ``--dryrun-mesh``: lower the distributed train step for an assigned arch
    on the production mesh (delegates to launch/dryrun.py machinery).

Usage:
    python -m repro.launch.train --arch canonicalizer-100m --steps 300
    python -m repro.launch.train --arch qwen3-32b --dryrun-mesh multi
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="canonicalizer-100m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=192)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config of the arch")
    ap.add_argument("--dryrun-mesh", choices=["single", "multi"], default=None)
    args = ap.parse_args()

    if args.dryrun_mesh:
        from .dryrun import force_placeholder_devices, run_cell

        force_placeholder_devices()
        res = run_cell(args.arch, "train_4k", args.dryrun_mesh)
        print(res)
        return

    import jax

    from ..configs.registry import get, reduced
    from ..training.data import BatchIterator, build_pairs
    from ..training.tokenizer import build_tokenizer
    from ..training.train_lib import TrainConfig, train
    from ..workloads import nyc_tlc, ssb, tpcds

    cfg = reduced(args.arch) if args.reduced else get(args.arch)
    wls = [ssb.build(n_fact=1000), nyc_tlc.build(n_fact=1000), tpcds.build(n_fact=1000)]
    tok = build_tokenizer(wls)
    if cfg.vocab < tok.vocab_size:
        raise SystemExit(f"arch vocab {cfg.vocab} < tokenizer {tok.vocab_size}")
    pairs = build_pairs(wls)
    print(f"[train] {len(pairs)} NL->signature pairs, vocab {tok.vocab_size}")
    batches = BatchIterator(pairs, tok, args.batch, args.seq_len)
    tcfg = TrainConfig(steps=args.steps, microbatches=args.microbatches,
                       ckpt_dir=args.ckpt_dir, grad_compression=args.grad_compression)
    out = train(cfg, tcfg, batches, key=jax.random.PRNGKey(0))
    print(f"[train] done; final loss {out['history'][-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
