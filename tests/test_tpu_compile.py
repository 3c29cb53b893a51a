"""Every Pallas kernel compiles for a TPU v5e chip at real model widths.

The TPU compiler ships with libtpu, which compiles for a described chip
without one attached: each test lowers a kernel (or a whole gradient)
with shapes placed on one device of a described ``v5e:2x2`` topology and
asserts the compiled HLO holds the kernel (``tpu_custom_call``).  That
catches what interpret mode cannot: block shapes the TPU tiling refuses,
primitives Mosaic does not lower, kernels that overflow VMEM.  Nothing
runs, so numbers are checked elsewhere (``tests/test_kernels.py``,
``tests/test_paged_kernels.py`` and ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process may hold libtpu, and every test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels import (decode_attention as da, flash_attention as fa,
                           grouped_matmul as gm, ops,
                           paged_decode_attention as pda,
                           ragged_prefill_attention as rpa, rglru_scan as rs,
                           ssd_scan as ss)
from repro.models import model as M
from repro.serve import engine as E
from repro.serve.paged_kv import PagedKVConfig, StatePool

QWEN = get_config("qwen2-0.5b")
H, KV, D = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
BS, N, W, B = 16, 512, 34, 8          # page size, pool pages, table, seats


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no libtpu / no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


BF, I32 = jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("window", [None, 64])
def test_paged_decode_compiles(one_chip, window):
    _compile(one_chip,
             lambda q, k, v, t, n: pda.paged_decode_attention(
                 q, k, v, t, n, block_size=BS, window=window),
             ((B, 1, H, D), BF), ((N, BS, KV * D), BF),
             ((N, BS, KV * D), BF), ((B, W), I32), ((B,), I32))


@pytest.mark.parametrize("rows,chunk", [(4, 128), (1, 32), (2, 24)])
def test_ragged_prefill_compiles(one_chip, rows, chunk):
    _compile(one_chip,
             lambda q, k, v, t, s, n: rpa.ragged_prefill_attention(
                 q, k, v, t, s, n, block_size=BS),
             ((rows, chunk, H, D), BF), ((N, BS, KV * D), BF),
             ((N, BS, KV * D), BF), ((rows, W), I32), ((rows,), I32),
             ((rows,), I32))


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([a-z][\w\-]*)\(")
# ops that only pass a buffer along: no bytes of the pool move
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _lanes(line):
    """Minor dim of an instruction's (first) result shape."""
    return int(re.search(r"\[([\d,]*)\]", line.split(" = ", 1)[1])
               .group(1).split(",")[-1])


def _page_axis_ops(hlo, sizes):
    """``(opcode, line)`` of every instruction whose result has a dim in
    ``sizes`` (the pool's page axis, alone or merged with its layers)."""
    hits = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m and any(sizes & {int(d) for d in dims.split(",") if d}
                     for dims in re.findall(r"\[([\d,]*)\]", m.group(1))):
            hits.append((m.group(2), line.strip()))
    return hits


def _in_place_case(arch):
    """``(cfg, seats, table width, step kwargs)`` of a model cut in depth
    whose paged pool holds 8,193 pages (the benchmark cells')."""
    if arch == "qwen2-0.5b":
        return dataclasses.replace(QWEN, num_layers=2), B, W, {}
    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(base, num_layers=3, moe=dataclasses.replace(
        base.moe, experts_held=8))
    return cfg, 32, 4096 // BS, dict(moe_dispatch="ragged")


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-v2-lite"])
def test_paged_steps_update_pool_in_place(one_chip, arch, program,
                                          monkeypatch):
    """The real paged step programs over an 8,193-page pool (like any
    served pool, too large for VMEM): qwen2-0.5b cut to 2 layers (K/V
    pages), DeepSeek-V2-Lite cut to 3, the dense layer and two MoE
    layers (MLA latents; the MoE segment stacks two layers, so its
    second reads at a page offset).  No dynamic-slice or
    dynamic-update-slice of a layer or of the whole pool, in the layer
    loop or around it; the kernels read the scattered pool directly.

    Over a lane-dense leaf (K/V pages, MLA's 512-lane ``ckv``) the only
    op that moves bytes is the token scatter, in place
    (``aliasing_operands``): no copy either.  MLA's 64-lane ``krope``
    leaf is the exception: the TPU's default layout for a bf16 array
    with a minor dim under 128 puts the page axis minor, and the kernel
    reads pages row-major, so XLA converts the whole stacked leaf where
    the program takes and returns it (a copy each way, once per call)."""
    monkeypatch.setattr(ops, "_MODE", "pallas")   # the chip's lowering
    cfg, seats, Wt, kw = _in_place_case(arch)
    pages = 8193
    pcfg = PagedKVConfig(block_size=BS, num_blocks=pages,
                         max_blocks_per_req=Wt, dtype="bfloat16")
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt,
                                                      sharding=one_chip)
    params = on_chip(jax.eval_shape(
        lambda: M.init_model(cfg, jax.random.PRNGKey(0))))
    pool = on_chip(jax.eval_shape(
        lambda: StatePool(cfg, pcfg, num_slots=seats).state))
    stacked = {a.shape[0] for a in jax.tree.leaves(pool)}
    assert max(stacked) == 2
    if program == "decode":
        step, _ = E.make_paged_serve_step(cfg, None, None, block_size=BS,
                                          kernels="fused", **kw)
        args = (params, arg((seats, 1)), arg((seats,)), pool,
                arg((seats, Wt)), arg((seats,), jnp.bool_))
    else:
        step, _ = E.make_paged_prefill_step(cfg, None, None, block_size=BS,
                                            kernels="fused", **kw)
        args = (params, arg((4, 128)), arg((4,)), arg((4,)), arg((4,)),
                pool, arg((4, Wt)))
    hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    moved = [(op, line) for op, line in
             _page_axis_ops(hlo, {pages, 2 * pages})
             if op not in _PLUMBING]
    sliced = [line for op, line in moved
              if "dynamic" in op + line.split(" = ")[0]]
    assert not sliced, sliced
    dense = [(op, line) for op, line in moved if _lanes(line) % 128 == 0]
    fusions = [line for op, line in dense if op == "fusion"]
    assert fusions and all("aliasing_operands" in f for f in fusions), \
        fusions
    assert {op for op, _ in dense} <= {"fusion", "scatter"}, dense
    if arch == "qwen2-0.5b":
        assert len(dense) == len(moved)           # every leaf lane-dense


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_deepseek_v2_lite_paged_steps_compile(one_chip, program, monkeypatch):
    """DeepSeek-V2-Lite's paged step programs at published widths, YaRN
    and the published gate, one chip's share of 8 routed experts, 32 seats
    at 4,096 context (a pool of 8,193 latent pages), with the route counts
    the runtime fetches.  Depth is cut to 2 layers, the dense one and one
    MoE layer, to keep the compile short: the layer loop compiles each
    distinct layer once whatever the depth."""
    monkeypatch.setattr(ops, "_MODE", "pallas")   # the chip's lowering
    base = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(base, num_layers=2, moe=dataclasses.replace(
        base.moe, experts_held=8))
    seats, ctx = 32, 4096
    pcfg = PagedKVConfig(block_size=BS, num_blocks=seats * ctx // BS + 1,
                         max_blocks_per_req=ctx // BS, dtype="bfloat16")
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    arg = lambda shape, dt=I32: jax.ShapeDtypeStruct(shape, dt,
                                                      sharding=one_chip)
    params = on_chip(jax.eval_shape(
        lambda: M.init_model(cfg, jax.random.PRNGKey(0))))
    assert params["seg1"][0]["ffn"]["w_gate"].shape == (1, 8, 2048, 1408)
    assert params["seg1"][0]["ffn"]["router"].shape == (1, 2048, 64)
    pool = on_chip(jax.eval_shape(
        lambda: StatePool(cfg, pcfg, num_slots=seats).state))
    Wt = pcfg.max_blocks_per_req
    kw = dict(block_size=BS, kernels="fused", moe_dispatch="ragged")
    if program == "decode":
        step, _ = E.make_paged_serve_step(cfg, None, None, **kw)
        args = (params, arg((seats, 1)), arg((seats,)), pool,
                arg((seats, Wt)), arg((seats,), jnp.bool_))
    else:
        step, _ = E.make_paged_prefill_step(cfg, None, None, **kw)
        args = (params, arg((4, 128)), arg((4,)), arg((4,)), arg((4,)),
                pool, arg((4, Wt)))
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    assert "grouped_matmul" in hlo
    if program == "decode":
        assert "paged_mla_decode_attention" in hlo
    routes = jax.eval_shape(step, *args)[-1]
    assert routes.shape == (1, 8) and routes.dtype == jnp.int32


@pytest.mark.parametrize("cache,window", [(544, None), (544, 128),
                                          (513, None)])
def test_dense_decode_compiles(one_chip, cache, window):
    _compile(one_chip,
             lambda q, k, v, n: da.decode_attention(q, k, v, n,
                                                    window=window),
             ((B, 1, H, D), BF), ((B, cache, KV, D), BF),
             ((B, cache, KV, D), BF), ((B,), I32))


@pytest.mark.parametrize("seq", [1024, 300, 45])
def test_flash_forward_compiles(one_chip, seq):
    _compile(one_chip, lambda q, k, v: fa.flash_attention(q, k, v),
             ((4, seq, H, D), BF), ((4, seq, KV, D), BF),
             ((4, seq, KV, D), BF))


def test_flash_traced_row_offsets_compile(one_chip):
    """The composed prefill's per-row ``q_offset`` is a traced vector
    under vmap (``models.attention.flash_rows``)."""
    def rows(q, k, v, starts):
        return jax.vmap(lambda a, b, c, o: fa.flash_attention(
            a[None], b[None], c[None], q_offset=o)[0])(q, k, v, starts)
    _compile(one_chip, rows, ((4, 128, H, D), BF), ((4, 544, KV, D), BF),
             ((4, 544, KV, D), BF), ((4,), I32))


def test_flash_gradient_compiles(one_chip):
    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()
    _compile(one_chip, jax.value_and_grad(loss, argnums=(0, 1, 2)),
             ((4, 1024, H, D), BF), ((4, 1024, KV, D), BF),
             ((4, 1024, KV, D), BF))


def test_mla_paged_decode_compiles(one_chip):
    cfg = get_config("deepseek-v2-lite-16b")
    R, r = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    _compile(one_chip,
             lambda ql, qr, c, kr, t, n: pda.paged_mla_decode_attention(
                 ql, qr, c, kr, t, n, block_size=BS, scale=0.07),
             ((B, cfg.num_heads, R), BF), ((B, cfg.num_heads, r), BF),
             ((N, BS, R), BF), ((N, BS, r), BF), ((B, W), I32), ((B,), I32))


@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_scan_compiles(one_chip, with_state):
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    nh, P, Nst, S = s.num_heads(cfg.d_model), s.head_dim, s.d_state, 2048
    shapes = [((1, S, nh, P), BF), ((1, S, nh), jnp.float32),
              ((nh,), jnp.float32), ((1, S, Nst), BF), ((1, S, Nst), BF)]
    if with_state:
        shapes.append(((1, nh, P, Nst), jnp.float32))
    _compile(one_chip,
             lambda x, dt, A, Bm, Cm, *s0: ss.ssd_scan(
                 x, dt, A, Bm, Cm, chunk=s.chunk_size,
                 init_state=s0[0] if s0 else None),
             *shapes)


def test_scan_gradients_compile(one_chip):
    """Training SSD and RG-LRU layers differentiates through the kernels."""
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    nh, P, Nst, S = s.num_heads(cfg.d_model), s.head_dim, s.d_state, 1024
    Wd = get_config("recurrentgemma-2b").rglru.lru_width

    def loss(x, dt, A, Bm, Cm, xr, ig, ag, la):
        y, _ = ss.ssd_scan(x, dt, A, Bm, Cm, chunk=s.chunk_size)
        h, _ = rs.rglru_scan(xr, ig, ag, la)
        return y.astype(jnp.float32).sum() + h.astype(jnp.float32).sum()
    _compile(one_chip, jax.value_and_grad(loss, argnums=tuple(range(9))),
             ((1, S, nh, P), BF), ((1, S, nh), jnp.float32),
             ((nh,), jnp.float32), ((1, S, Nst), BF), ((1, S, Nst), BF),
             ((1, S, Wd), BF), ((1, S, Wd), BF), ((1, S, Wd), BF),
             ((Wd,), jnp.float32))


@pytest.mark.parametrize("with_state", [False, True])
def test_rglru_scan_compiles(one_chip, with_state):
    Wd = get_config("recurrentgemma-2b").rglru.lru_width
    shapes = [((1, 2048, Wd), BF)] * 3 + [((Wd,), jnp.float32)]
    if with_state:
        shapes.append(((1, Wd), BF))
    _compile(one_chip,
             lambda x, i, a, la, *h0: rs.rglru_scan(
                 x, i, a, la, init_state=h0[0] if h0 else None),
             *shapes)


def test_grouped_matmul_compiles(one_chip):
    """An 8-expert share of deepseek-moe-16b's experts."""
    cfg = get_config("deepseek-moe-16b")
    Dm, F = cfg.d_model, cfg.moe.d_ff_expert
    _compile(one_chip, gm.grouped_matmul, ((2048, Dm), BF),
             ((8, Dm, F), BF), ((8,), I32))
