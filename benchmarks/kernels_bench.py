"""Kernel micro-bench: fused paged kernels vs composed lowering on CPU.

On this CPU container the interpret-mode numbers are NOT TPU performance —
they validate the kernels run and anchor the perf-model overhead factors
(``repro.kernels.perf_model``): for each paged case we measure the fused
(Pallas interpret) and composed (gather + dense XLA) lowerings, derive
each one's pure-work roofline seconds from the calibrated host speeds,
and emit ``overhead_factor = measured / pure`` into
``results/BENCH_kernels.json``.  Absolute CPU timings are noise across
hosts; the factors are stable enough for ``tools/bench_gate.py`` to gate
(a kernel that suddenly does 3x the work moves its factor 3x).  The
``tpu`` block projects the same analytic costs onto the v5e roofline —
the number the fused kernel exists for: composed/fused > 2x on decode
because the composed path reads the pool, writes the dense copy, and
reads it again.

Cases run at serving-realistic shapes: mixed lengths, partial last
pages, filler prefill rows — the data-dependent work the fused kernels
skip in-kernel and the perf model prices via pages-visited.
"""
import functools

import jax
import jax.numpy as jnp

from benchmarks.common import emit_json, row, time_call
from repro.core import topology
from repro.kernels import perf_model as PM, ref
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_mla_decode_attention)
from repro.kernels.ragged_prefill_attention import ragged_prefill_attention


def _case(name, fused_fn, composed_fn, args, fused_cost, composed_cost,
          host, *, atol=2e-5):
    """Time both lowerings, check parity, derive overhead factors."""
    f_jit = jax.jit(fused_fn)
    c_jit = jax.jit(composed_fn)
    out_f = f_jit(*args)
    out_c = c_jit(*args)
    parity = bool(jnp.all(jnp.abs(out_f.astype(jnp.float32)
                                  - out_c.astype(jnp.float32)) < atol))
    t_f = time_call(f_jit, *args, iters=5)
    t_c = time_call(c_jit, *args, iters=5)
    pure_f = fused_cost.pure_seconds(host["flops_per_s"], host["bytes_per_s"])
    pure_c = composed_cost.pure_seconds(host["flops_per_s"],
                                        host["bytes_per_s"])
    tpu_f, tpu_c = fused_cost.tpu_seconds(), composed_cost.tpu_seconds()
    row(f"kernels.{name}.fused", t_f * 1e6,
        f"overhead x{t_f / pure_f:.0f}; TPU roofline {tpu_f*1e6:.2f}us")
    row(f"kernels.{name}.composed", t_c * 1e6,
        f"overhead x{t_c / pure_c:.0f}; TPU roofline {tpu_c*1e6:.2f}us")
    return {
        "fused": {"measured_s": t_f, "pure_s": pure_f,
                  "overhead_factor": t_f / pure_f,
                  "flops": fused_cost.flops, "hbm_bytes": fused_cost.hbm_bytes},
        "composed": {"measured_s": t_c, "pure_s": pure_c,
                     "overhead_factor": t_c / pure_c,
                     "flops": composed_cost.flops,
                     "hbm_bytes": composed_cost.hbm_bytes},
        "parity_ok": int(parity),
        "tpu": {"fused_us": tpu_f * 1e6, "composed_us": tpu_c * 1e6,
                "roofline_speedup": tpu_c / tpu_f},
    }


def paged_cases(host):
    """The three fused-kernel cases at serving-realistic shapes."""
    key = jax.random.PRNGKey(7)
    cases = {}
    item = 4                                 # f32

    # ---- paged decode: mixed lengths, partial last pages ----------------
    B, H, KV, D, bs, W, N = 4, 8, 4, 64, 16, 8, 64
    lengths = [100, 37, 128, 9]              # partial last pages everywhere
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D)) * 0.3
    kp = jax.random.normal(ks[1], (N, bs, KV * D)) * 0.3       # lane-dense
    vp = jax.random.normal(ks[2], (N, bs, KV * D)) * 0.3
    tables = jnp.arange(1, 1 + B * W, dtype=jnp.int32).reshape(B, W) % N
    lens = jnp.asarray(lengths, jnp.int32)
    pv = PM.decode_pages_visited(lengths, block_size=bs)
    cases["paged_decode"] = _case(
        "paged_decode",
        functools.partial(paged_decode_attention, block_size=bs,
                          interpret=True),
        functools.partial(ref.paged_decode_attention, block_size=bs),
        (q, kp, vp, tables, lens),
        PM.paged_decode_cost(batch=B, num_heads=H, kv_heads=KV, head_dim=D,
                             block_size=bs, pages_visited=pv, itemsize=item),
        PM.paged_decode_cost(batch=B, num_heads=H, kv_heads=KV, head_dim=D,
                             block_size=bs, pages_visited=pv, itemsize=item,
                             fused=False, table_width=W),
        host)
    cases["paged_decode"]["shape"] = dict(B=B, H=H, KV=KV, D=D,
                                          block_size=bs, W=W,
                                          lengths=lengths, pages_visited=pv)

    # ---- MLA paged decode over latent pools -----------------------------
    R, r = 64, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    ql = jax.random.normal(ks[0], (B, H, R)) * 0.3
    qr = jax.random.normal(ks[1], (B, H, r)) * 0.3
    ckv = jax.random.normal(ks[2], (N, bs, R)) * 0.3
    krp = jax.random.normal(ks[3], (N, bs, r)) * 0.3
    scale = (R + r) ** -0.5
    cases["mla_decode"] = _case(
        "mla_decode",
        functools.partial(paged_mla_decode_attention, block_size=bs,
                          scale=scale, interpret=True),
        functools.partial(ref.paged_mla_decode_attention, block_size=bs,
                          scale=scale),
        (ql, qr, ckv, krp, tables, lens),
        PM.mla_decode_cost(batch=B, num_heads=H, lora_rank=R, rope_dim=r,
                           block_size=bs, pages_visited=pv, itemsize=item),
        PM.mla_decode_cost(batch=B, num_heads=H, lora_rank=R, rope_dim=r,
                           block_size=bs, pages_visited=pv, itemsize=item,
                           fused=False, table_width=W),
        host)
    cases["mla_decode"]["shape"] = dict(B=B, H=H, R=R, r=r, block_size=bs,
                                        W=W, lengths=lengths,
                                        pages_visited=pv)

    # ---- ragged prefill: mixed starts, filler row -----------------------
    P, C = 4, 32
    starts_l = [0, 48, 16, 0]
    limits_l = [80, 120, 48, 0]              # last row is scheduler filler
    ks = jax.random.split(jax.random.PRNGKey(13), 1)
    qc = jax.random.normal(ks[0], (P, C, H, D)) * 0.3
    starts = jnp.asarray(starts_l, jnp.int32)
    limits = jnp.asarray(limits_l, jnp.int32)
    pvp = PM.prefill_pages_visited(starts_l, limits_l, C, block_size=bs,
                                   table_width=W)
    rows_live = sum(1 for x in limits_l if x > 0)
    cases["ragged_prefill"] = _case(
        "ragged_prefill",
        functools.partial(ragged_prefill_attention, block_size=bs,
                          interpret=True),
        functools.partial(ref.ragged_prefill_attention, block_size=bs),
        (qc, kp, vp, tables, starts, limits),
        PM.ragged_prefill_cost(rows_live=rows_live, chunk=C, num_heads=H,
                               kv_heads=KV, head_dim=D, block_size=bs,
                               pages_visited=pvp, itemsize=item),
        PM.ragged_prefill_cost(rows_live=rows_live, chunk=C, num_heads=H,
                               kv_heads=KV, head_dim=D, block_size=bs,
                               pages_visited=pvp, itemsize=item, fused=False,
                               rows_total=P, table_width=W),
        host, atol=1e-4)
    cases["ragged_prefill"]["shape"] = dict(P=P, C=C, H=H, KV=KV, D=D,
                                            block_size=bs, W=W,
                                            starts=starts_l, limits=limits_l,
                                            pages_visited=pvp)
    return cases


def run():
    key = jax.random.PRNGKey(0)
    # flash attention: production-ish tile
    B, S, H, KV, D = 1, 2048, 8, 8, 128
    ks = jax.random.split(key, 3)
    q = (jax.random.normal(ks[0], (B, S, H, D)) * 0.3).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], (B, S, KV, D)) * 0.3).astype(jnp.bfloat16)
    v = (jax.random.normal(ks[2], (B, S, KV, D)) * 0.3).astype(jnp.bfloat16)
    t = time_call(jax.jit(lambda q, k, v: ref.flash_attention(q, k, v)), q, k, v)
    flops = 4 * B * S * S * H * D / 2        # causal
    row("kernels.flash_ref_cpu", t * 1e6,
        f"TPU roofline {flops/topology.PEAK_FLOPS_BF16*1e6:.1f}us")

    # ssd scan
    B2, S2, Hh, P, N = 2, 2048, 16, 64, 128
    ks = jax.random.split(key, 5)
    x = (jax.random.normal(ks[0], (B2, S2, Hh, P)) * 0.3).astype(jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B2, S2, Hh)))
    A = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B2, S2, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B2, S2, N)) * 0.3
    t = time_call(jax.jit(lambda *a: ref.ssd_scan(*a, chunk=128)[0]),
                  x, dt, A, Bm, Cm)
    row("kernels.ssd_ref_cpu", t * 1e6, f"B{B2} S{S2} H{Hh} chunked")

    # grouped matmul
    T, Dd, F, E = 4096, 512, 1024, 16
    ks = jax.random.split(key, 2)
    xg = (jax.random.normal(ks[0], (T, Dd)) * 0.3).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (E, Dd, F)) * 0.3).astype(jnp.bfloat16)
    sizes = jnp.full((E,), T // E, jnp.int32)
    t = time_call(jax.jit(ref.grouped_matmul), xg, w, sizes)
    gf = 2 * T * Dd * F
    row("kernels.gmm_ref_cpu", t * 1e6,
        f"TPU roofline {gf/topology.PEAK_FLOPS_BF16*1e6:.1f}us")

    # fused paged kernels + perf-model overhead factors
    host = PM.calibrate_host()
    payload = {"host": host, "cases": paged_cases(host)}
    emit_json("BENCH_kernels.json", payload)
    return payload


if __name__ == "__main__":
    run()
