#!/usr/bin/env python3
"""Chip smoke: qwen2-0.5b at full width through HyperServe and the trainer.

    python chip_smoke.py              # one TPU chip, every phase below
    python chip_smoke.py --chips 4    # four chips: the fabric path only

One process drives every phase on one device, in this order:

  device     the first JAX device must be a TPU; otherwise exit 1 at once
  serve      16 requests (prompts 64-512 tokens, 32 new each) through
             Supernode -> session.serve(plans.serve(...)), kernels "auto";
             the compiled decode and prefill steps must hold the Pallas
             kernels (``tpu_custom_call``)
  host tier  the same server shape with a pool too small for the working
             set: preemptions spill pages to ``pinned_host`` memory and the
             tokens equal the unconstrained run
  parity     a float32 copy of the model, 4 requests x 16 greedy tokens,
             Pallas kernels vs the pure-jnp ``ref`` oracles: identical
  kernels    paged decode, ragged prefill, flash forward and backward at
             real widths against ``kernels/ref.py``
  train      3 AdamW steps of 4 x 1024 tokens through session.train;
             finite losses, step 1 against the ``ref`` oracles

``--chips 4`` serves the serve phase's requests through
``plans.fabric(replicas=4)`` (a Router over four one-chip replicas) and
compares them with one replica in the same process.

Weights and prompts are random, made from ``--seed``.  Times printed here
come from one cold run and are not a benchmark.  Every failed check
raises; the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
BLOCK = 16                     # tokens per page
SEATS = 8
POOL_BLOCKS = 512              # 196,608 B per block over 24 layers in bf16
N_REQ, PROMPT_LO, PROMPT_HI, MAX_NEW = 16, 64, 512, 32
TABLE = -(-(PROMPT_HI + MAX_NEW) // BLOCK)
CHUNK, PREFILL_ROWS = 128, 4
TIGHT_BLOCKS = 48              # host-tier phase: below its working set
PARITY_REQ, PARITY_NEW = 4, 16
TRAIN_ROWS, TRAIN_SEQ, TRAIN_STEPS = 4, 1024, 3


def log(*a):
    print(*a, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def serve_config(**over):
    from repro.configs.base import ServeConfig
    kw = dict(block_size=BLOCK, num_blocks=POOL_BLOCKS,
              max_blocks_per_req=TABLE, max_slots=SEATS,
              prefill_chunk=CHUNK, prefill_batch=PREFILL_ROWS,
              prefill_chunks_per_step=PREFILL_ROWS)
    kw.update(over)
    return ServeConfig(**kw)


def make_prompts(cfg, n, lo, hi, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=int(n_)).tolist()
            for n_ in rng.integers(lo, hi + 1, size=n)]


def init_params(cfg, seed):
    import jax
    from repro.models import model as M
    return jax.jit(functools.partial(M.init_model, cfg))(
        jax.random.PRNGKey(seed))


def serve_requests(session, cfg, params, scfg, prompts, max_new,
                   on_step=None):
    """Serve ``prompts`` to completion; returns (server, token lists)."""
    from repro.api import plans
    srv = session.serve(cfg, params, plan=plans.serve(serve=scfg))
    rids = [srv.submit(p, max_new) for p in prompts]
    if on_step is None:
        out = srv.join()
    else:
        while srv.engine.scheduler.has_work():
            srv.step_once()
            on_step(srv)
        out = {r: srv.result(r) for r in rids}
    toks = [out[r] for r in rids]
    check(all(len(t) == max_new for t in toks),
          f"not every request got {max_new} tokens: {[len(t) for t in toks]}")
    check(all(0 <= x < cfg.vocab_size for t in toks for x in t),
          "a token id outside the vocabulary")
    return srv, toks


def step_hlo(srv, prefill_rows):
    """Compiled HLO text of the server's decode and prefill steps."""
    import jax.numpy as jnp
    eng = srv.engine
    scfg = eng.scfg
    B, W, C = scfg.max_slots, scfg.max_blocks_per_req, scfg.prefill_chunk
    z = lambda *s: jnp.zeros(s, jnp.int32)
    dec = eng._decode_step.lower(
        eng.params, z(B, 1), z(B), eng.pool.state, z(B, W),
        jnp.zeros((B,), bool)).compile().as_text()
    P = prefill_rows
    pre = eng._prefill_step.lower(
        eng.params, z(P, C), z(P), z(P), z(P), eng.pool.state,
        z(P, W)).compile().as_text()
    return dec, pre


def compile_clock():
    """Seconds JAX spent tracing, lowering and compiling, as a callable."""
    import jax
    total = [0.0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_serve(session, cfg, params, prompts, clock, dev):
    c0, t0 = clock(), time.perf_counter()
    srv, toks = serve_requests(session, cfg, params, serve_config(), prompts,
                               MAX_NEW)
    wall = time.perf_counter() - t0
    path = srv.engine.kernel_path
    check(path == "fused", f"kernel_path={path}, want fused on TPU")
    dec, pre = step_hlo(srv, PREFILL_ROWS)
    check("tpu_custom_call" in dec, "decode step holds no Pallas kernel")
    check("tpu_custom_call" in pre, "prefill step holds no Pallas kernel")
    st = srv.stats()
    log(f"serve: kernel_path={path} requests={len(toks)} "
        f"tokens_served={sum(map(len, toks))} "
        f"prefill_calls={st['prefill_calls']} "
        f"compile_s={clock() - c0:.1f} wall_s={wall:.1f} "
        f"(one cold smoke run, not a benchmark) "
        f"decode_tpu_custom_call={dec.count('tpu_custom_call')} "
        f"prefill_tpu_custom_call={pre.count('tpu_custom_call')} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    return toks


def phase_host_tier(session, cfg, params, prompts, host_kind):
    """Constrained pool: preempted pages live in host memory, and the
    tokens equal an unconstrained run.  One prefill row per call in both
    runs, so every request's arithmetic is the same program either way."""
    base = dict(prefill_batch=1, prefill_chunks_per_step=1,
                enable_prefix_cache=False)
    _, want = serve_requests(session, cfg, params, serve_config(**base),
                             prompts, MAX_NEW)
    kinds = set()
    srv, got = serve_requests(
        session, cfg, params,
        serve_config(num_blocks=TIGHT_BLOCKS, **base), prompts, MAX_NEW,
        on_step=lambda s: kinds.update(
            s.engine.blocks.archive.host_memory_kinds()))
    st = srv.stats()
    log(f"host_tier: pool_blocks={TIGHT_BLOCKS} "
        f"preemptions={st['preemptions']} "
        f"restores={int(srv.obs().metrics.counter('serve.restores').value)} "
        f"archived_memory_kinds={sorted(kinds)} "
        f"tokens_match={got == want}")
    check(st["preemptions"] >= 1, "the tight pool preempted nothing")
    check(kinds == {host_kind},
          f"archived arrays live in {sorted(kinds)}, want {host_kind}")
    check(got == want, "preempted run's tokens differ from the "
          "unconstrained run's")


def first_divergence(cfg, params, prompts, a, b):
    """(request, step, top-2 logit gap) of the first token that differs."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    for i, (x, y) in enumerate(zip(a, b)):
        for s, (u, v) in enumerate(zip(x, y)):
            if u != v:
                seq = jnp.asarray([prompts[i] + x[:s]], jnp.int32)
                logits, _, _ = M.forward(params, seq, cfg, mode="train",
                                         remat=False)
                top2 = jax.lax.top_k(
                    logits[0, -1, :cfg.vocab_size].astype(jnp.float32), 2)[0]
                return i, s, float(top2[0] - top2[1])
    return None


def phase_parity(session, cfg, params, prompts):
    import jax
    from repro.kernels import ops
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype("float32"), params)
    scfg = serve_config(num_blocks=4 * TABLE + 8)
    with jax.default_matmul_precision("highest"):
        srv, kern = serve_requests(session, cfg32, p32, scfg, prompts,
                                   PARITY_NEW)
        check(srv.engine.kernel_path == "fused", "parity run not fused")
        ops.set_mode("ref")
        try:
            _, oracle = serve_requests(session, cfg32, p32, scfg, prompts,
                                       PARITY_NEW)
        finally:
            ops.set_mode("auto")
        if kern != oracle:
            ops.set_mode("ref")
            try:
                req, step, gap = first_divergence(cfg32, p32, prompts, kern,
                                                  oracle)
            finally:
                ops.set_mode("auto")
            log(f"parity: first difference at request {req} step {step}, "
                f"top-2 logit gap {gap:.3e}")
            raise AssertionError("float32 tokens differ between the Pallas "
                                 "kernels and the ref oracles")
    log(f"parity: float32 requests={len(prompts)} tokens={PARITY_NEW} each "
        f"pallas_equals_ref=True")


def kernel_errors(cfg, seed, dtype):
    """Max abs error of each chip kernel against its float32 oracle at
    qwen2-0.5b attention widths, on inputs of ``dtype``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config
    from repro.kernels import ops, ref
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    rnd = lambda *s: (jax.random.normal(next(ks), s) * 0.5).astype(dtype)
    f32 = lambda *a: [x.astype(jnp.float32) for x in a]
    rng = np.random.default_rng(seed)
    err = lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
    hi = functools.partial(jax.default_matmul_precision, "highest")
    errs = {}
    # the table walk may visit any page: distinct random pages per row;
    # pages are lane-dense, every KV head of a row side by side
    N, B, P, C, S = POOL_BLOCKS, SEATS, PREFILL_ROWS, CHUNK, 1024
    kp, vp = rnd(N, BLOCK, KV * D), rnd(N, BLOCK, KV * D)
    tab = jnp.asarray(rng.permutation(np.arange(1, N))[:B * TABLE]
                      .reshape(B, TABLE), jnp.int32)
    lens = jnp.asarray(rng.integers(1, TABLE * BLOCK + 1, size=B), jnp.int32)
    q = rnd(B, 1, H, D)
    got = ops.paged_decode_attention(q, kp, vp, tab, lens, block_size=BLOCK)
    with hi():
        want = ref.paged_decode_attention(*f32(q, kp, vp), tab, lens,
                                          block_size=BLOCK)
    errs["paged_decode"] = err(got, want)
    starts = jnp.asarray([0, C, 3 * C, 0], jnp.int32)
    limits = jnp.asarray([C - 5, 3 * C, 4 * C, 0], jnp.int32)  # one filler
    qc = rnd(P, C, H, D)
    got = ops.ragged_prefill_attention(qc, kp, vp, tab[:P], starts, limits,
                                       block_size=BLOCK)
    with hi():
        want = ref.ragged_prefill_attention(*f32(qc, kp, vp), tab[:P],
                                            starts, limits, block_size=BLOCK)
    errs["ragged_prefill"] = err(got, want)
    qf, kf, vf = rnd(2, S, H, D), rnd(2, S, KV, D), rnd(2, S, KV, D)
    got = ops.flash_attention(qf, kf, vf)
    with hi():
        want = ref.flash_attention(*f32(qf, kf, vf))
    errs["flash_fwd"] = err(got, want)
    if dtype == jnp.float32:
        ct = rnd(2, S, H, D)
        loss = lambda f: lambda q_, k_, v_: jnp.sum(f(q_, k_, v_) * ct)
        gs = jax.grad(loss(ops.flash_attention), (0, 1, 2))(qf, kf, vf)
        with hi():
            gwant = jax.grad(loss(ref.flash_attention), (0, 1, 2))(qf, kf, vf)
        errs["flash_bwd"] = max(err(g, w) for g, w in zip(gs, gwant))
    # the kernels no chip phase serves, at their own models' widths; their
    # outputs are not bounded near 1, so these errors are relative
    rel = lambda a, b: err(a, b) / float(jnp.max(jnp.abs(b)))
    m2 = get_config("mamba2-370m")
    nh, hp, ns = (m2.ssm.num_heads(m2.d_model), m2.ssm.head_dim,
                  m2.ssm.d_state)
    x, Bm, Cm = rnd(1, 512, nh, hp), rnd(1, 512, ns), rnd(1, 512, ns)
    dt = jax.nn.softplus(rnd(1, 512, nh).astype(jnp.float32))
    A = -jnp.exp(rnd(nh).astype(jnp.float32))
    s0 = rnd(1, nh, hp, ns).astype(jnp.float32)
    got = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=m2.ssm.chunk_size,
                       init_state=s0)
    with hi():
        want = ref.ssd_scan(*f32(x), dt, A, *f32(Bm, Cm),
                            chunk=m2.ssm.chunk_size, init_state=s0)
    errs["ssd_scan_rel"] = max(rel(g, w) for g, w in zip(got, want))
    lw = get_config("recurrentgemma-2b").rglru.lru_width
    xs = rnd(1, 512, lw)
    ig, ag = (jax.nn.sigmoid(rnd(1, 512, lw)) for _ in range(2))
    la = -jax.nn.softplus(rnd(lw).astype(jnp.float32))
    h0 = rnd(1, lw)
    got = ops.rglru_scan(xs, ig, ag, la, init_state=h0)
    with hi():
        want = ref.rglru_scan(*f32(xs, ig, ag), la, init_state=h0)
    errs["rglru_scan_rel"] = max(rel(g, w) for g, w in zip(got, want))
    moe = get_config("deepseek-moe-16b")
    xt = rnd(512, moe.d_model)
    wt = rnd(8, moe.d_model, moe.moe.d_ff_expert)
    sizes = [100, 0, 65, 47, 150, 1, 99, 50]
    got = ops.grouped_matmul(xt, wt, jnp.asarray(sizes, jnp.int32))
    # per-expert slices (the ref.py oracle gathers a (T, D, F) weight copy)
    offs = np.cumsum([0] + sizes)
    with hi():
        want = jnp.concatenate([
            f32(xt[a:b])[0] @ f32(wt[e])[0]
            for e, (a, b) in enumerate(zip(offs[:-1], offs[1:]))])
    errs["grouped_matmul_rel"] = rel(got, want)
    return errs


def phase_kernels(cfg, seed):
    """Float32 under "highest" precision (kernel and oracle alike), and
    bfloat16, the serving dtype, at the default precision."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        errs = {f"{k}_f32": v
                for k, v in kernel_errors(cfg, seed, jnp.float32).items()}
    errs.update({f"{k}_bf16": v
                 for k, v in kernel_errors(cfg, seed, jnp.bfloat16).items()})
    log("kernels: max_err (abs; _rel relative) " + " ".join(
        f"{k}={v:.3e}" for k, v in errs.items()))
    for k, v in errs.items():
        tol = KERNEL_TOL.get(k, KERNEL_TOL[k.rsplit("_", 1)[1]])
        check(v < tol, f"{k} error {v:.3e} >= {tol:.0e}")


# Outputs are softmax averages of N(0, 0.25) values, so |out| < ~1.5.
#  f32:  kernel and oracle both multiply in float32 (the MXU's fp32
#        contract precision under "highest"); they differ in summation
#        order only, over pages of 16 keys (D = 64, <= 1024 keys), which
#        float32 rounding bounds at a few ulp of 1.0 per step.  The
#        backward is the oracle's own VJP on the same inputs.
#  bf16: the kernel rounds p to bf16 before p.v (2^-9 relative) and
#        rounds its output to bf16 (2^-9 of |out| <= 1.5, ~3e-3); the
#        oracle keeps float32 throughout.
# The scans and grouped matmul compute in float32 from the same inputs as
# their oracles and differ in order only (f32), or also round the output
# to bf16 (2^-9 relative); 1e-4 and 2e-2 of the largest output hold both,
# except SSD in f32: its log-decays sum over a 256-step chunk to ~1e2, and
# f32 rounding of those sums moves exp() by up to ~1e-4 relative.
KERNEL_TOL = {"f32": 1e-4, "bf16": 2e-2, "ssd_scan_rel_f32": 1e-3}
# step-1 loss of random bf16 weights (~ln V = 11.9): bf16 activations
# round at 2^-9 relative; averaged over 4096 tokens the kernel-vs-oracle
# difference in the mean cross-entropy stays far below 1e-2.
TRAIN_LOSS_TOL = 1e-2


def phase_train(session, cfg, dev):
    import math

    from repro.api import plans
    from repro.configs.base import ShapeConfig
    from repro.kernels import ops
    from repro.train.trainer import TrainConfig
    shape = ShapeConfig("chip_smoke", TRAIN_SEQ, TRAIN_ROWS, "train")
    run = lambda n: session.train(
        cfg, shape, plan=plans.fsdp_tp(),
        train_cfg=TrainConfig(num_steps=n, log_every=1))[1]
    hist = run(TRAIN_STEPS)
    losses = [h["loss"] for h in hist]
    check(len(losses) == TRAIN_STEPS, f"history has {len(losses)} steps")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    ops.set_mode("ref")
    try:
        ref_loss = run(1)[0]["loss"]
    finally:
        ops.set_mode("auto")
    diff = abs(losses[0] - ref_loss)
    log(f"train: rows={TRAIN_ROWS} seq={TRAIN_SEQ} losses={losses} "
        f"step1_ref_loss={ref_loss} step1_diff={diff:.3e} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    check(diff < TRAIN_LOSS_TOL, f"step-1 loss differs from ref by {diff}")


def phase_fabric(cfg, params, prompts, devs):
    """plans.fabric(replicas=4): a Router over four one-chip replicas,
    against one replica in the same process.  One prefill row per call,
    so a request's arithmetic does not depend on which requests share
    its replica."""
    import jax

    from repro.api import Supernode, plans
    scfg = serve_config(prefill_batch=1, prefill_chunks_per_step=1)
    t0 = time.perf_counter()
    router = Supernode((1, 4), devices=devs[:4]).fabric(
        cfg, params, plan=plans.fabric(replicas=4, serve=scfg))
    fids = [router.submit(p, MAX_NEW) for p in prompts]
    out = router.join()
    got = [out[f] for f in fids]
    wall = time.perf_counter() - t0
    placed = []
    for rep in router.replicas:
        eng = rep.engine
        ds = {d for a in jax.tree.leaves((eng.params, eng.pool.state))
              for d in a.sharding.device_set}
        placed.append(ds)
    served = [sum(1 for _, _, r in router.dispatch_log if r == i)
              for i in range(len(router.replicas))]
    _, want = serve_requests(Supernode(None, devices=devs[:1]), cfg, params,
                             scfg, prompts, MAX_NEW)
    log(f"fabric: replicas={len(router.replicas)} "
        f"devices={[sorted(d.id for d in s) for s in placed]} "
        f"requests_per_replica={served} "
        f"tokens_served={sum(map(len, got))} wall_s={wall:.1f} "
        f"(one cold smoke run, not a benchmark) "
        f"tokens_equal_one_replica={got == want}")
    check(all(len(s) == 1 for s in placed), "a replica spans devices")
    check(len(set().union(*placed)) == 4, "replicas share a device")
    check(all(n >= 1 for n in served), f"idle replica: {served}")
    check(got == want, "fabric tokens differ from the one-replica run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repo's src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: {e}".splitlines()[0], file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} compile_cache={compile_cache.enable()}")

    from repro.api import Supernode
    from repro.configs.base import get_config
    from repro.core.compat import host_memory_kind
    cfg = get_config(ARCH)
    params = init_params(cfg, args.seed)
    prompts = make_prompts(cfg, N_REQ, PROMPT_LO, PROMPT_HI, args.seed)
    if args.chips == 4:
        phase_fabric(cfg, params, prompts, devs)
    else:
        dev = devs[0]
        session = Supernode(None, devices=devs[:1])
        clock = compile_clock()
        phase_serve(session, cfg, params, prompts, clock, dev)
        phase_host_tier(session, cfg, params,
                        make_prompts(cfg, SEATS, PROMPT_LO, 256, args.seed + 1),
                        host_memory_kind())
        phase_parity(session, cfg, params, prompts[:PARITY_REQ])
        del params
        phase_kernels(cfg, args.seed)
        phase_train(session, cfg, dev)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
