"""Serving cells: weights from the seed, HyperServe for the cell's
deployment (``cells/<cell>.json``: ``serve``), a warm-up of every shape,
then the mix's generator (``generators/<kind>.py``) drives the window
through the client.  After the window: the device's peak, the traced
window's records for the per-layer readers, and the comparison of what
the window served with the float32 reference (``check``)."""
from __future__ import annotations

import gc
import shutil

from chipbench import check, layers, serving, spec, stats, trace
from chipbench import weights as W
from chipbench.costs import Dims
from chipbench.harness import CompileCounter, Outcome, device_info, log, now


def _log_window(win, *, setup_s, warm, compiles, device, pool_tokens):
    submitted = [f for f in win.flights if f.t_submit is not None]
    done = [f for f in win.flights if f.done]
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    log(f"setup: setup_s={setup_s} warm_up_tokens={warm} "
        f"compiles_in_window={compiles} peak_bytes_in_use="
        f"{device['memory_peak_bytes']}")
    late = stats.lateness_summary(win.lateness)
    if late:
        log(f"generator: lateness_median_s={late['median_s']} "
            f"lateness_max_s={late['max_s']}")
    log(f"requests: due={len(win.flights)} submitted={len(submitted)} "
        f"rejected={sum(f.rejected for f in win.flights)} "
        f"first_token={sum(1 for f in win.flights if f.times)} "
        f"finished={len(done)} finished_in_window="
        f"{sum(1 for f in done if f.times[-1] >= win.t_open)} "
        f"steps={len(win.steps)} window_s={win.seconds} "
        f"tokens={stats.window_tokens(win)}")
    live = [sum(s.decode_lengths) for s in win.steps]
    if live:
        log(f"kv: decoding_tokens_mean={sum(live) / len(live)} "
            f"decoding_tokens_max={max(live)} pool_tokens={pool_tokens}")
    ttft, itl = stats.ttfts(win), stats.itls(win)
    if ttft and itl:
        log(f"tails (not judged): ttft_p50_s={stats.percentile(ttft, 50)} "
            f"ttft_p99_s={stats.percentile(ttft, 99)} "
            f"itl_p50_s={stats.percentile(itl, 50)} "
            f"itl_p99_s={stats.percentile(itl, 99)} "
            f"itl_count={len(itl)} ttft_count={len(ttft)}")


def run(job) -> Outcome:
    import jax
    cell, deploy = job.cell, job.cell.deploy
    dims = Dims.from_config(cell.config)
    pcfg = serving.program_config(cell.config)
    w = W.make(W.seed_key(job.seed), dims=dims, vocab_rows=pcfg.padded_vocab,
               dtype=cell.config["torch_dtype"])
    jax.block_until_ready(w)
    server = serving.build_server(job.devices, cell.config, deploy, w)
    scfg = server.engine.scfg
    warm = serving.warm_up(server, deploy, dims.vocab, job.seed)
    counter = CompileCounter()
    tr = server.obs().trace
    offset_s = 0.0
    if job.traced:
        tr.enable(capacity=1 << 20)
        tr.instant("chipbench.clock")
        offset_s = now() - tr.events()[-1]["ts"] / 1e6
        if job.trace_dir.exists():
            shutil.rmtree(job.trace_dir)
    client = serving.Client(
        server, trace_dir=str(job.trace_dir) if job.traced else None)
    gen = spec.load(job.root, "generators", cell.traffic["kind"])
    n0 = counter.n
    win = gen.window(client, cell.traffic, seed=job.seed,
                     seconds=job.seconds, vocab=dims.vocab, deploy=deploy)
    setup_s = win.t_open - job.t_start
    compiles = counter.n - n0
    device = device_info(job.devices, cell.chips)
    _log_window(win, setup_s=setup_s, warm=warm, compiles=compiles,
                device=device,
                pool_tokens=(scfg.num_blocks - 1) * scfg.block_size)

    ctx = None
    if job.traced:
        lo, hi = win.trace_from, win.trace_to
        metas = [server.request_meta(f.rid) for f in win.flights
                 if f.rid is not None]
        waits = [m["queue_wait_s"] for m in metas
                 if m["queue_wait_s"] is not None
                 and lo <= m["t_enqueue"] <= hi]
        prefills = serving.program_prefills(
            tr.events(), client.by_rid, lo, hi,
            deploy["serve"]["prefill_chunk"], offset_s)
        decodes = [s.decode_lengths for s in win.steps
                   if lo <= s.t0 and s.t1 <= hi]
        path = trace.latest_xspace(str(job.trace_dir))
        reduced = (trace.reduce(trace.events_from_xspace(path),
                                layers.KERNELS) if path else {})
        shutil.rmtree(job.trace_dir, ignore_errors=True)
        ctx = layers.Context(dims=dims, peaks=job.peaks, chips=cell.chips,
                             block=scfg.block_size, trace=reduced,
                             decode_calls=decodes, prefill_calls=prefills,
                             queue_waits=waits)
    del server, client
    gc.collect()

    limits = deploy["check"]["limits"]
    picked = check.sample(win.flights, job.seed, deploy["check"]["requests"])
    gaps = (check.gaps(w, dims, picked, control=job.control) if picked
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
