"""RL post-training launcher — HyperRL through the Supernode session API.

Colocated actor/learner (one mesh, the default):

    PYTHONPATH=src python -m repro.launch.rl --arch qwen2-0.5b --reduced \
        --iters 3 --prompts 2 --group-size 4 --max-new 8 [--explain]

Actor/learner role disaggregation (needs >= 2 devices):

    PYTHONPATH=src python -m repro.launch.rl --arch qwen2-0.5b --reduced \
        --plan rl_disagg

The toy reward scores token diversity (distinct tokens per rollout) —
enough within-group variance to give GRPO a gradient, and you can watch
``reward_mean`` move while ``weights_version`` ticks once per iteration.  ``--explain`` prints the learner-side plan
resolution report (every leaf's spec + rule) and exits.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.api import PlanError, Supernode, plans
from repro.configs.base import RLConfig, ServeConfig, get_config
from repro.launch import compile_cache
from repro.models import model as M


def rl_plan(args):
    scfg = ServeConfig(block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       max_blocks_per_req=max(
                           4, -(-(args.prompt_len + args.max_new)
                                // args.block_size) + 1),
                       max_slots=args.slots,
                       prefill_chunk=args.prefill_chunk,
                       enable_prefix_cache=False)
    rcfg = RLConfig(group_size=args.group_size,
                    prompts_per_iter=args.prompts,
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    lr=args.lr, iterations=args.iters)
    return plans.get(args.plan)(serve=scfg, rl=rcfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--plan", default="rl_colocate",
                    choices=["rl_colocate", "rl_disagg"])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--prompts", type=int, default=2,
                    help="prompt groups per iteration")
    ap.add_argument("--group-size", type=int, default=4,
                    help="GRPO samples per prompt")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--seed", type=int, default=0)
    # serving-leg knobs (the actor's paged pool)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--explain", action="store_true",
                    help="print the plan resolution report and exit")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="capture a HyperTrace timeline and write "
                         "Perfetto/Chrome trace_event JSON here")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.plan == "rl_disagg" and len(jax.devices()) < 2:
        raise SystemExit("--plan rl_disagg needs >= 2 devices "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 to try on CPU)")
    session = Supernode.auto()
    if args.trace:
        session.obs().trace.enable()
    plan = rl_plan(args)
    try:
        if args.explain:
            print(session.explain(plan, cfg, batch=args.slots))
            return
        params = M.init_model(cfg, jax.random.PRNGKey(args.seed))
        rl = session.rl(cfg, plan=plan, params=params, seed=args.seed)

        rng = np.random.default_rng(args.seed)

        def prompts_fn(_it):
            return [rng.integers(1, cfg.vocab_size,
                                 size=args.prompt_len).tolist()
                    for _ in range(args.prompts)]

        def reward_fn(prompt, tokens):
            return float(len(set(tokens)))     # diversity: distinct tokens

        def hook(m):
            print(f"iter {m['iter']}: loss={m['loss']:+.4f} "
                  f"reward={m['reward_mean']:.2f} "
                  f"rollout {m['rollout_tokens']} tok in {m['rollout_s']:.2f}s "
                  f"publish {m['publish_s']*1e3:.1f}ms "
                  f"v{int(m['weights_version'])}")

        rl.run(prompts_fn, reward_fn, iterations=args.iters, hook=hook)
        util = rl.utilization_report()
        if util:
            print("per-role busy seconds:",
                  {k: round(v, 3) for k, v in util.items()})
        st = rl.stats()
        print(f"done: {int(st['tokens_generated'])} rollout tokens, "
              f"{int(st['learner_updates'])} updates, "
              f"weights v{int(st['weights_version'])}")
    except PlanError as e:
        raise SystemExit(f"{type(e).__name__}: {e}")
    finally:
        if args.trace:
            tr = session.obs().trace
            print(f"trace: {tr.export(args.trace)} "
                  f"({len(tr.events())} events, {tr.dropped} dropped)")


if __name__ == "__main__":
    main()
