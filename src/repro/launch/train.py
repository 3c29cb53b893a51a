"""Training launcher — a thin shell over the Supernode session API.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --shape train_4k [--reduced] [--steps 100] [--offload] \
        [--plan fsdp_tp|tp_only|offload_all|pipeline|pipeline_fsdp] \
        [--pipeline STAGES --micro-batches M] [--explain] \
        [--moe-dispatch gshard|ragged] [--mesh auto|none]

On this CPU container use ``--reduced`` (the full configs are exercised by
the dry-run); on a real slice drop it and pass ``--mesh auto``.
``--explain`` prints the plan-resolution report (every leaf's spec, memory
tier and rule) and exits without training.
"""
from __future__ import annotations

import argparse

from repro.api import Supernode, plans
from repro.configs.base import SHAPES, ShapeConfig, get_config
from repro.launch import compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--plan", default="fsdp_tp",
                    choices=["fsdp_tp", "tp_only", "offload_all",
                             "pipeline", "pipeline_fsdp"],
                    help="HyperPlan training preset to resolve")
    ap.add_argument("--offload", action="store_true",
                    help="HyperOffload: params+opt state on host")
    ap.add_argument("--pipeline", type=int, default=0, metavar="STAGES",
                    help="Mpipe: pipeline-parallel 1F1B over STAGES stage "
                         "groups (adds a pipeline leg to the chosen plan)")
    ap.add_argument("--micro-batches", type=int, default=4,
                    help="micro-batches per step for --pipeline")
    ap.add_argument("--explain", action="store_true",
                    help="print the plan resolution report and exit")
    ap.add_argument("--moe-dispatch", default="gshard",
                    choices=["gshard", "ragged"])
    ap.add_argument("--mesh", default="none", choices=["none", "auto"])
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        shape = ShapeConfig("reduced", 64, 4, "train")
    else:
        shape = SHAPES[args.shape]

    session = Supernode.auto() if args.mesh == "auto" else Supernode()
    # ONE declaration: --offload sets the plan, and the trainer derives the
    # fetch/offload schedule from it (no parallel OffloadConfig to drift)
    plan = plans.get(args.plan)()
    if args.pipeline:
        from repro.configs.base import PipelineConfig
        plan = plan.replace(pipeline=PipelineConfig(
            stages=args.pipeline, micro_batches=args.micro_batches))
    if args.offload:
        plan = plan.replace(params_on_host=True, opt_state_on_host=True)

    if args.explain:
        print(session.explain(plan, cfg, batch=shape.global_batch))
        return

    def log(m):
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"grad_norm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
              f"{m['wall_s']:.1f}s", flush=True)

    session.train(cfg, shape, plan=plan,
                  adamw=AdamWConfig(lr=args.lr, total_steps=args.steps),
                  train_cfg=TrainConfig(
                      num_steps=args.steps, log_every=10,
                      ckpt_every=args.steps if args.ckpt_dir else 0,
                      ckpt_dir=args.ckpt_dir or "/tmp/repro_ckpt"),
                  moe_dispatch=args.moe_dispatch, hook=log)


if __name__ == "__main__":
    main()
