"""Serving cells of a latent-attention MoE model held at one chip's expert
share (DeepSeek-V2): weights from the seed (``weights_mla_moe``),
HyperServe for the cell's deployment through ``Supernode.serve``, a
warm-up of every shape, then the mix's generator drives the window
through the client, as ``drivers/serve.py`` does.  The client also keeps
each step's held-expert route counts, which the step programs return.
After the window: the device's peak, the traced window's records for the
per-layer readers (``layers_mla_moe``), and the comparison of what the
window served with this architecture's float32 reference
(``reference_mla_moe``)."""
from __future__ import annotations

import gc
import shutil

from chipbench import check, layers_mla_moe, serving, spec, trace
from chipbench import reference_mla_moe as reference
from chipbench import weights_mla_moe as W
from chipbench.costs_mla_moe import Dims
from chipbench.harness import CompileCounter, Outcome, device_info, log


class Client(serving.Client):
    """``serving.Client`` that keeps on each ``Step`` (``routes``) the
    route counts of the step programs that step ran."""

    def step(self, win) -> None:
        super().step(win)
        win.steps[-1].routes = list(self.server.engine.moe_step_routes)


def build_server(devices, conf: dict, deploy: dict, weights: dict):
    from repro.api import Supernode, plans
    scfg = serving.serve_config(deploy, Dims.from_config(conf))
    session = Supernode(None, devices=list(devices[:1]))
    return session.serve(W.program_config(conf), W.program_params(weights),
                         plan=plans.serve(serve=scfg))


def run(job) -> Outcome:
    import jax
    cell, deploy = job.cell, job.cell.deploy
    dims = Dims.from_config(cell.config)
    pcfg = W.program_config(cell.config)
    w = W.make(W.seed_key(job.seed), dims=dims, vocab_rows=pcfg.padded_vocab,
               dtype=cell.config["torch_dtype"])
    jax.block_until_ready(w)
    server = build_server(job.devices, cell.config, deploy, w)
    scfg = server.engine.scfg
    warm = serving.warm_up(server, deploy, dims.vocab, job.seed)
    counter = CompileCounter()
    if job.traced:
        server.obs().trace.enable(capacity=1 << 20)
        if job.trace_dir.exists():
            shutil.rmtree(job.trace_dir)
    client = Client(server,
                    trace_dir=str(job.trace_dir) if job.traced else None)
    gen = spec.load(job.root, "generators", cell.traffic["kind"])
    n0 = counter.n
    win = gen.window(client, cell.traffic, seed=job.seed,
                     seconds=job.seconds, vocab=dims.vocab, deploy=deploy)
    setup_s = win.t_open - job.t_start
    compiles = counter.n - n0
    device = device_info(job.devices, cell.chips)
    spec.load(job.root, "drivers", "serve")._log_window(
        win, setup_s=setup_s, warm=warm, compiles=compiles, device=device,
        pool_tokens=(scfg.num_blocks - 1) * scfg.block_size)
    stats = server.stats()
    log("moe: " + " ".join(f"{k}={stats[k]}" for k in (
        "moe_routes_held", "moe_routes", "moe_experts_held",
        "moe_experts_total")))

    ctx = None
    if job.traced:
        lo, hi = win.trace_from, win.trace_to
        steps = [s for s in win.steps if lo <= s.t0 and s.t1 <= hi]
        path = trace.latest_xspace(str(job.trace_dir))
        reduced = (trace.reduce(trace.events_from_xspace(path),
                                layers_mla_moe.KERNELS) if path else {})
        shutil.rmtree(job.trace_dir, ignore_errors=True)
        ctx = layers_mla_moe.Context(
            dims=dims, peaks=job.peaks, chips=cell.chips,
            block=scfg.block_size, trace=reduced,
            decode_calls=[s.decode_lengths for s in steps],
            prefill_calls=[], queue_waits=[],
            routes=[r for s in steps for r in s.routes])
    del server, client
    gc.collect()

    limits = deploy["check"]["limits"]
    picked = check.sample(win.flights, job.seed, deploy["check"]["requests"])
    gaps = (reference.gaps(w, dims, picked, control=job.control) if picked
            else {"served": [], "control": []})
    out = Outcome(
        setup_s=setup_s, window=win, attempted=len(win.flights),
        failed=sum(f.rejected for f in win.flights), device=device,
        check=check.judge(win.flights, picked, dims, gaps["served"], limits),
        context=ctx)
    if job.control:
        out.control = check.judge(win.flights, picked, dims, gaps["control"],
                                  limits)
        out.readings = {"program": check.gap_numbers(gaps["served"]),
                        "control": check.gap_numbers(gaps["control"])}
    return out
