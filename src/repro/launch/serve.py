"""Serving launcher — fixed-batch generation or the HyperServe runtime,
both through the Supernode session API.

Fixed batch:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --prompt-len 16 --max-new 32

Continuous batching over the paged KV pool, with staggered arrivals:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --continuous --requests 8 --max-new 16 [--disaggregate] [--explain]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import PlanError, Supernode, plans
from repro.configs.base import ServeConfig, get_config
from repro.launch import compile_cache
from repro.models import model as M


def serve_plan(args):
    scfg = ServeConfig(block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       max_blocks_per_req=max(
                           4, -(-(args.prompt_len + args.max_new)
                                // args.block_size) + 1),
                       max_slots=args.slots,
                       prefill_chunk=args.prefill_chunk,
                       kernels=args.kernels)
    if args.disaggregate:
        return plans.serve_disagg(serve=scfg)
    return plans.serve(serve=scfg)


def run_fixed(session, cfg, params, args):
    prompts = np.ones((args.batch, args.prompt_len), np.int32)
    t0 = time.perf_counter()
    out = session.generate(cfg, params, prompts,
                           max_new_tokens=args.max_new,
                           temperature=args.temperature,
                           max_len=args.prompt_len + args.max_new + 8,
                           window_override=args.window or None)
    dt = time.perf_counter() - t0
    n_new = args.batch * args.max_new
    print(f"generated {n_new} tokens in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s on this host)")
    print("first sequence:", out[0].tolist())


def run_continuous(session, cfg, params, args):
    serve = session.serve(cfg, params, plan=serve_plan(args))
    rng = np.random.default_rng(0)
    rids = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        rids.append(serve.submit(prompt, int(rng.integers(
            args.max_new // 2, args.max_new + 1)),
            temperature=args.temperature))
        # stagger arrivals: interleave a couple of engine steps per submit
        for _ in range(2):
            serve.step_once()
    out = serve.join()
    dt = time.perf_counter() - t0
    st = serve.stats()
    n_new = sum(len(out[r]) for r in rids)
    print(f"served {len(rids)} requests, {n_new} tokens in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s on this host)")
    print(f"peak-free blocks={st['free_blocks']} "
          f"preemptions={st['preemptions']} prefix_hits={st['prefix_hits']}")
    print("first request tokens:", out[rids[0]])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window decode cache (0 = full)")
    # HyperServe runtime
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV pool")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--kernels", default="auto",
                    choices=("auto", "fused", "composed"),
                    help="paged attention lowering: fused Pallas kernels "
                         "(in-kernel block-table walk; interpret mode off-"
                         "TPU) or the composed gather+dense path; auto = "
                         "fused on TPU only")
    ap.add_argument("--disaggregate", action="store_true",
                    help="prefill/decode role split over device subgroups")
    ap.add_argument("--explain", action="store_true",
                    help="print the serving plan resolution report and exit")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="capture a HyperTrace timeline and write "
                         "Perfetto/Chrome trace_event JSON here "
                         "(open at https://ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus metrics dump after the run")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.disaggregate and len(jax.devices()) < 2:
        raise SystemExit("--disaggregate needs >= 2 devices "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 to try on CPU)")
    session = Supernode.auto()
    obs = session.obs()
    if args.trace:
        obs.trace.enable()
    try:
        if args.explain:
            # includes one row per serving-state leaf: paged / slot /
            # windowed(w=N) kind + the derive_pool rule that fired
            print(session.explain(serve_plan(args), cfg, batch=args.slots,
                                  for_serving=True))
            return
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        if args.continuous:
            run_continuous(session, cfg, params, args)
        else:
            run_fixed(session, cfg, params, args)
    except PlanError as e:
        # typed validation (ServePlanError et al.): the message already
        # names the offending mixer/rule — surface it without a traceback
        raise SystemExit(f"{type(e).__name__}: {e}")
    finally:
        if args.trace:
            # export validates the payload before writing (assert inside)
            print(f"trace: {obs.trace.export(args.trace)} "
                  f"({len(obs.trace.events())} events, "
                  f"{obs.trace.dropped} dropped)")
        if args.metrics:
            print(obs.metrics.dump_prometheus(), end="")


if __name__ == "__main__":
    main()
