"""Training driver: ``python -m repro.launch.train --arch smollm-135m ...``

On the CPU container this runs reduced configs end-to-end with a simulated
heterogeneous cluster (the paper's scheduler visibly rebalancing).  On real
hardware the same driver runs the full config under the production mesh
(``--production`` adds pjit shardings from repro.distributed.sharding).
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import RunConfig, get_arch, get_shape, reduced
from repro.configs.base import ShapeConfig
from repro.distributed.simulated_cluster import SimulatedCluster, WorkerSpec
from repro.launch.cache import enable_compile_cache
from repro.train.trainer import Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8_ef", "topk_ef"])
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", seq_len=args.seq_len,
                        global_batch=args.global_batch, kind="train")
    run = RunConfig(
        model=cfg, shape=shape, checkpoint_dir=args.ckpt_dir,
        total_steps=max(args.steps, 1), warmup_steps=max(args.steps // 10, 1),
        checkpoint_every=max(args.steps // 2, 1),
        grad_compression=args.compression,
    )
    # heterogeneous simulated fleet: a fast, two mediums, one slow worker
    rng = np.random.default_rng(0)
    specs = [
        WorkerSpec(mu=float(m), sigma=float(s))
        for m, s in zip(
            rng.uniform(5.0, 20.0, args.workers),
            rng.uniform(0.5, 2.0, args.workers),
        )
    ]
    trainer = Trainer(run, cluster=SimulatedCluster(specs),
                      num_microbatches=args.microbatches)
    if args.resume and trainer.try_restore():
        print(f"resumed from step {trainer.step}")
    report = trainer.train(args.steps)
    print(f"steps={report.steps} loss: {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")
    if report.splits:
        print("final microbatch split:", report.splits[-1])
    if report.makespans:
        k = max(len(report.makespans) // 4, 1)
        print(
            "mean simulated makespan: first-quarter %.2f -> last-quarter %.2f"
            % (float(np.mean(report.makespans[:k])), float(np.mean(report.makespans[-k:])))
        )


if __name__ == "__main__":
    main()
