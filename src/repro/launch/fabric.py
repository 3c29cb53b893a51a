"""HyperFabric launcher — a multi-tenant serving fabric through the session
API: N HyperServe replicas on carved submeshes, SLO-class weighted-fair
dispatch, prefix-affinity routing, elastic scale.

    PYTHONPATH=src python -m repro.launch.fabric --arch qwen2-0.5b --reduced \
        --replicas 2 --requests 12 --max-new 16 [--elastic] [--explain]

A mixed two-tenant workload is synthesised: ``chat`` (interactive SLO,
short prompts sharing a common system prefix — exercises affinity) and
``bulk`` (batch SLO, long prompts).  ``--explain`` prints the resolution
report including the replica->submesh carve rows and exits.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import PlanError, Supernode, plans
from repro.configs.base import FabricConfig, ServeConfig, TenantSpec, get_config
from repro.launch import compile_cache
from repro.models import model as M


def fabric_plan(args):
    scfg = ServeConfig(block_size=args.block_size,
                       num_blocks=args.num_blocks,
                       max_slots=args.slots,
                       prefill_chunk=args.prefill_chunk)
    fcfg = FabricConfig(
        replicas=args.replicas,
        split=tuple(int(s) for s in args.split.split(",")) if args.split
        else (),
        tenants=(TenantSpec("chat", slo="interactive"),
                 TenantSpec("bulk", slo="batch")),
        max_pending=args.max_pending,
        elastic=args.elastic)
    return plans.fabric(serve=scfg, fabric=fcfg)


def run(session, cfg, params, args):
    fab = session.fabric(cfg, params, plan=fabric_plan(args))
    rng = np.random.default_rng(0)
    system = rng.integers(1, cfg.vocab_size,
                          size=2 * args.block_size).tolist()
    # warm the shared system prompt: prefix blocks are retained at request
    # FINISH, so one completed chat request seeds the CoW cache the rest
    # of the chat traffic can affinity-route to
    fab.submit(system + [7, 9], 2, tenant="chat")
    fab.join()
    fids = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        if i % 3 == 2:   # every third request is bulk traffic
            plen = int(rng.integers(24, 48))
            prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
            fids.append(fab.submit(prompt, args.max_new, tenant="bulk"))
        else:            # chat shares the system prompt -> affinity routing
            tail = rng.integers(1, cfg.vocab_size, size=6).tolist()
            fids.append(fab.submit(system + tail, args.max_new,
                                   tenant="chat"))
        fab.step()       # stagger arrivals one router step apart
    out = fab.join()
    dt = time.perf_counter() - t0
    st = fab.stats()
    n_new = sum(len(out[f]) for f in fids)
    print(f"fabric served {len(fids)} requests ({n_new} tokens) over "
          f"{st['active_replicas']} active replicas in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s on this host)")
    print(f"dispatched={st['dispatched']} affinity_hits="
          f"{st['affinity_hits']} rejected={st['rejected']} "
          f"scale_up={st['scale_up']} scale_down={st['scale_down']}")
    chat = [f for f in fids if fab.request_meta(f)["tenant"] == "chat"]
    ttfts = [fab.request_meta(f)["ttft_steps"] for f in chat]
    print(f"chat (interactive) TTFT in router steps: {ttfts}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--split", default="",
                    help="explicit devices per replica, e.g. '4,2' "
                         "(heterogeneous carve; each count must divide "
                         "the model dims, e.g. vocab); empty = even split")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-pending", type=int, default=64)
    ap.add_argument("--elastic", action="store_true",
                    help="drain idle replicas / re-activate on queue depth")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--explain", action="store_true",
                    help="print the plan resolution report (incl. the "
                         "replica->submesh carve) and exit")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Perfetto/Chrome trace of the front door")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus metrics dump after the run")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    session = Supernode.auto()
    obs = session.obs()
    if args.trace:
        obs.trace.enable()
    try:
        if args.explain:
            print(session.explain(fabric_plan(args), cfg, batch=args.slots,
                                  for_serving=True))
            return
        params = M.init_model(cfg, jax.random.PRNGKey(0))
        run(session, cfg, params, args)
    except PlanError as e:
        raise SystemExit(f"{type(e).__name__}: {e}")
    finally:
        if args.trace:
            print(f"trace: {obs.trace.export(args.trace)} "
                  f"({len(obs.trace.events())} events, "
                  f"{obs.trace.dropped} dropped)")
        if args.metrics:
            print(obs.metrics.dump_prometheus(), end="")


if __name__ == "__main__":
    main()
