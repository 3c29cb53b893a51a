"""Paged KV block manager: alloc/free/CoW/spill invariants (HyperServe)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.serve.paged_kv import (BlockManager, NoFreeBlocks, PagedKVConfig,
                                  PagedKVPool, blocks_for)


def _mgr(num_blocks=8, block_size=4):
    return BlockManager(PagedKVConfig(block_size=block_size,
                                      num_blocks=num_blocks))


def test_blocks_for():
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2
    assert blocks_for(16, 4) == 4


def test_alloc_free_invariants():
    m = _mgr(num_blocks=8)
    assert m.num_total == 7                    # null block excluded
    a = m.alloc(3)
    b = m.alloc(2)
    assert len(set(a) | set(b)) == 5           # all distinct
    assert 0 not in a + b                      # null block never handed out
    assert m.num_free == 2
    assert 0.0 < m.occupancy() <= 1.0
    m.free(a)
    assert m.num_free == 5
    m.free(b)
    assert m.num_free == 7
    assert m.occupancy() == 0.0


def test_alloc_exhaustion_raises_and_preserves_state():
    m = _mgr(num_blocks=4)
    m.alloc(3)
    assert not m.can_alloc(1)
    with pytest.raises(NoFreeBlocks):
        m.alloc(1)
    assert m.num_free == 0


def test_double_free_asserts():
    m = _mgr()
    [b] = m.alloc(1)
    m.free([b])
    with pytest.raises(AssertionError):
        m.free([b])


def test_freeing_null_block_is_noop():
    m = _mgr()
    free0 = m.num_free
    m.free([0])
    assert m.num_free == free0


def test_cow_fork_and_refcounts():
    m = _mgr(num_blocks=8)
    table = m.alloc(3)
    shared = m.fork(table)
    assert shared == table
    assert all(m.refcount(b) == 2 for b in table)
    assert all(m.is_shared(b) for b in table)
    # one owner frees: blocks stay allocated for the other
    m.free(table)
    assert all(m.refcount(b) == 1 for b in table)
    assert m.num_free == 4
    m.free(shared)
    assert m.num_free == 7


def test_cow_write_fault_copies_shared_block():
    m = _mgr(num_blocks=8)
    table = m.alloc(2)
    fork = m.fork(table)
    copies = []
    new_table, wb = m.ensure_writable(fork, 1, lambda s, d: copies.append((s, d)))
    assert copies == [(table[1], wb)]
    assert wb != table[1]                       # repointed to a fresh block
    assert new_table[0] == table[0]             # untouched entry still shared
    assert m.refcount(table[1]) == 1            # old block back to one owner
    assert m.refcount(wb) == 1
    # exclusively-owned block: no copy, no repoint
    solo = m.alloc(1)
    new2, wb2 = m.ensure_writable(solo, 0, lambda s, d: copies.append(0))
    assert wb2 == solo[0] and len(copies) == 1


def test_spill_restore_roundtrip_preserves_pages():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    pcfg = PagedKVConfig(block_size=2, num_blocks=8, max_blocks_per_req=4,
                         dtype="float32")
    pool = PagedKVPool(cfg, pcfg, dtype=jnp.float32)
    m = BlockManager(pcfg)
    table = m.alloc(2)
    # write recognisable content into the pages
    marked = jax.tree.map(
        lambda a: a.at[:, jnp.asarray(table)].set(1.5), pool.kv)
    pool.kv = marked
    want = jax.tree.leaves(pool.extract_pages(table))[0]

    m.spill(("req", 0), table, pool.extract_pages)
    assert m.num_free == 7                      # blocks returned to pool
    assert m.archive.nbytes() > 0
    # dirty the (now free) blocks to prove restore really rewrites them
    pool.kv = jax.tree.map(lambda a: a * 0, pool.kv)

    new_table = m.restore(("req", 0), pool.insert_pages)
    assert len(new_table) == 2
    got = jax.tree.leaves(pool.extract_pages(new_table))[0]
    assert (got == want).all()
    assert m.archive.nbytes() == 0              # archive entry consumed


def test_restore_without_space_keeps_archive():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    pcfg = PagedKVConfig(block_size=2, num_blocks=4, max_blocks_per_req=4,
                         dtype="float32")
    pool = PagedKVPool(cfg, pcfg, dtype=jnp.float32)
    m = BlockManager(pcfg)
    table = m.alloc(2)
    m.spill(("req", 1), table, pool.extract_pages)
    m.alloc(3)                                  # someone else took the pool
    with pytest.raises(NoFreeBlocks):
        m.restore(("req", 1), pool.insert_pages)
    assert m.spilled(("req", 1))                # entry still intact


def test_state_pool_layouts_per_family():
    """The mixer registry resolves every family to its state layout."""
    from repro.models import mixers as MX
    from repro.serve.paged_kv import StatePool

    # pure-slot: SSD keeps O(1) recurrent state, no paged leaves at all
    ssm = get_config("mamba2-370m").reduced()
    pool = StatePool(ssm, PagedKVConfig(), num_slots=3)
    assert pool.layout.has_slot_state and not pool.layout.has_paged_state
    assert pool.layout.free_window is None and not pool.layout.pure_paged
    leaves = jax.tree.leaves(pool.state)
    assert leaves and all(a.shape[1] == 3 for a in leaves)   # (L, slots, ...)

    # hybrid: RG-LRU slot state + windowed local attention
    hyb = dataclasses.replace(get_config("recurrentgemma-2b").reduced(),
                              num_layers=3)
    layout = MX.model_state_layout(hyb)
    assert layout.has_slot_state and layout.has_paged_state
    assert layout.free_window == hyb.sliding_window

    # MLA: paged latents, disagg-capable
    mla = get_config("deepseek-v2-lite-16b").reduced()
    layout = MX.model_state_layout(mla)
    assert layout.pure_paged and not layout.has_slot_state

    # full + windowed attention mix: windowed freeing is unsound (full-attn
    # layers need every page) AND the dense-prefill disagg handoff is
    # unsound (ring-layout LOCAL_ATTN prefill cache) -> neither free_window
    # nor pure_paged
    mix = dataclasses.replace(
        hyb, rglru=dataclasses.replace(hyb.rglru,
                                       block_pattern=("attn", "local",
                                                      "attn")))
    layout = MX.model_state_layout(mix)
    assert layout.has_windowed_state and not layout.has_slot_state
    assert layout.free_window is None and not layout.pure_paged


def test_unregistered_mixer_is_typed_serve_error():
    """An unknown mixer kind is a ServePlanError naming mixer and rule."""
    from repro.api.errors import ServePlanError
    from repro.models import mixers as MX

    cfg = get_config("recurrentgemma-2b").reduced()
    bogus = dataclasses.replace(
        cfg, num_layers=3,
        rglru=dataclasses.replace(cfg.rglru,
                                  block_pattern=("rglru", "bogus", "local")))
    with pytest.raises(ServePlanError, match="bogus.*StateSpec"):
        MX.model_state_layout(bogus)


def test_pool_hbm_accounting():
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    pcfg = PagedKVConfig(block_size=4, num_blocks=16, dtype="float32")
    pool = PagedKVPool(cfg, pcfg, dtype=jnp.float32)
    # 2 layers x (k + v) x N x bs x KV x hd x 4 bytes
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    want = cfg.num_layers * 2 * 16 * 4 * kv * hd * 4
    assert pool.hbm_bytes() == want


def _ssd_attn_hybrid():
    """SSD and full attention alternating in one segment."""
    rg = get_config("recurrentgemma-2b").reduced()
    return dataclasses.replace(
        rg, dtype="float32", num_layers=4,
        ssm=get_config("mamba2-370m").reduced().ssm,
        rglru=dataclasses.replace(rg.rglru, block_pattern=("ssd", "attn")))


@pytest.mark.parametrize("arch,want", [
    ("qwen2-0.5b", (2, 0)),               # k, v in place
    ("granite-3-2b", (2, 0)),
    ("deepseek-v2-lite-16b", (4, 0)),     # ckv, krope x 2 segments
    ("ssd-attn", (2, 2)),                 # k, v in place; state, conv sliced
])
def test_pool_leaves_in_place_or_sliced(arch, want):
    """Paged leaves are updated in place: attention pages lane-dense,
    (L, N, bs, KV*hd), MLA latents (L, N, bs, R) / (.., rope); slot
    leaves are sliced per layer and written back."""
    cfg = (_ssd_attn_hybrid() if arch == "ssd-attn"
           else get_config(arch).reduced())
    pcfg = PagedKVConfig(block_size=4, num_blocks=16)
    pool = PagedKVPool(cfg, pcfg, num_slots=2)
    assert pool.leaf_counts() == want
    for seg in pool.layout.segments:
        for j, spec in enumerate(seg.specs):
            if not spec.in_place:
                continue
            leaves = pool.state[seg.name][j]
            if spec.kind == "mla":
                widths = {"ckv": cfg.mla.kv_lora_rank,
                          "krope": cfg.mla.qk_rope_head_dim}
            else:
                widths = dict.fromkeys(
                    "kv", cfg.num_kv_heads * cfg.resolved_head_dim)
            assert {n: a.shape for n, a in leaves.items()} == {
                n: (seg.repeat, 16, 4, w) for n, w in widths.items()}


def test_seat_prefill_caches_lands_heads_side_by_side():
    """A dense (L, B, S, KV, hd) prefill cache seats into lane-dense
    pages: position p of the request is row p % bs of its table's page
    p // bs, every KV head side by side."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    pcfg = PagedKVConfig(block_size=4, num_blocks=16, dtype="float32")
    pool = PagedKVPool(cfg, pcfg, dtype=jnp.float32)
    L, KV, hd, S = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim, 7
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    dense = {n: jax.random.normal(k, (L, 2, S, KV, hd))
             for n, k in zip("kv", ks)}
    pool.seat_prefill_caches({"seg0": (dense,)}, [5, 9], S, row=1)
    for n in "kv":
        a = pool.state["seg0"][0][n]
        for p in range(S):
            got = a[:, [5, 9][p // 4], p % 4]
            assert (got == dense[n][:, 1, p].reshape(L, KV * hd)).all()
        assert (a[:, 9, 3] == 0).all()           # past the prompt: untouched


@pytest.mark.parametrize("kernels", ["composed", "fused"])
def test_layer_offset_writes_each_layers_own_pages(kernels):
    """One decode step over two stacked attention layers: layer l's token
    lands in page ``table[pos // bs]`` of layer l, an empty seat's dummy
    token in layer l's own null block 0, and no other page changes."""
    from repro.models import model as M
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype="float32")
    assert cfg.num_layers == 2
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    pcfg = PagedKVConfig(block_size=4, num_blocks=12, dtype="float32")
    pool = PagedKVPool(cfg, pcfg, dtype=jnp.float32)
    tables = jnp.asarray([[3, 7, 0], [0, 0, 0]], jnp.int32)
    _, new = M.decode_step_paged(
        params, jnp.asarray([[11], [0]], jnp.int32),
        jnp.asarray([5, 0], jnp.int32), cfg, pool.state, tables,
        block_size=4, slot_mask=jnp.asarray([True, False]), kernels=kernels)
    for n in "kv":
        a = new["seg0"][0][n]                    # (L, N, bs, KV*hd)
        written = jnp.abs(a).sum(-1) > 0         # (L, N, bs)
        want = jnp.zeros_like(written).at[:, 7, 1].set(True) \
            .at[:, 0, 0].set(True)
        assert (written == want).all(), n
        assert not jnp.allclose(a[0, 7, 1], a[1, 7, 1])   # two layers


@pytest.mark.parametrize("kernels", ["composed", "fused"])
def test_mla_hooks_update_one_stacked_layer(kernels):
    """MLA's paged decode and prefill hooks at ``layer=1`` of a stacked
    three-layer latent pool give what the same hooks give on layer 1
    sliced out by hand, and leave layers 0 and 2 bit-identical."""
    from repro.models import mla
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").reduced(),
                              dtype="float32")
    m = cfg.mla
    p = mla.init_mla(cfg, jax.random.PRNGKey(0))
    L, N, bs = 3, 10, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    kv = {"ckv": jax.random.normal(ks[0], (L, N, bs, m.kv_lora_rank)),
          "krope": jax.random.normal(ks[1], (L, N, bs, m.qk_rope_head_dim))}
    one = {n: a[1:2] for n, a in kv.items()}         # layer 1, by hand
    tables = jnp.asarray([[3, 7, 0], [5, 2, 9]], jnp.int32)
    kw = dict(block_size=bs, kernels=kernels)
    calls = {
        "decode": lambda pool, layer: mla.mla_decode_paged(
            p, jax.random.normal(ks[2], (2, 1, cfg.d_model)),
            jnp.asarray([6, 9], jnp.int32), cfg, pool, tables, layer=layer,
            **kw),
        # row 1 runs past its limit: those positions go to the null block
        "prefill": lambda pool, layer: mla.mla_prefill_chunk_paged(
            p, jax.random.normal(ks[3], (2, 4, cfg.d_model)),
            jnp.asarray([4, 0], jnp.int32), jnp.asarray([8, 3], jnp.int32),
            cfg, pool, tables, layer=layer, **kw),
    }
    for name, call in calls.items():
        y, new = call(kv, 1)
        y_one, new_one = call(one, 0)
        assert jnp.array_equal(y, y_one), name
        for n in kv:
            assert new[n].shape == kv[n].shape
            assert jnp.array_equal(new[n][1], new_one[n][0]), (name, n)
            assert not jnp.array_equal(new[n][1], kv[n][1]), (name, n)
            for other in (0, 2):
                assert jnp.array_equal(new[n][other], kv[n][other]), \
                    (name, n, other)


# ---------------------------------------------------------------------------
# BlockManager invariants under random op sequences (mini-hypothesis)
# ---------------------------------------------------------------------------
from hypothesis import given, settings, strategies as st  # noqa: E402


# no deadline: the first example pays a one-off warm-up (~300 ms against
# Hypothesis' 200 ms default) that the same example does not pay again
@settings(max_examples=40, deadline=None)
@given(st.data())
def test_block_manager_invariants_random_ops(data):
    """Free-list conservation, refcounts, CoW and window-freeing semantics
    hold under arbitrary interleavings of alloc / free / fork / CoW-write /
    spill / restore / window-free."""
    num_blocks = data.draw(st.integers(4, 24), label="num_blocks")
    m = _mgr(num_blocks=num_blocks)
    tables = []                                  # live tables (lists of bids)
    spilled = {}                                 # key -> expected page count

    def check():
        # conservation: every non-null block is free XOR refcounted
        held = sum(1 for b in range(1, num_blocks) if m.refcount(b) > 0)
        assert m.num_free + held == m.num_total
        assert all(m.refcount(b) >= 0 for b in range(num_blocks))
        assert m.refcount(0) == 1                # null block pinned forever
        # every table entry is null or allocated
        for t in tables:
            for b in t:
                assert b == 0 or m.refcount(b) >= 1

    for _ in range(data.draw(st.integers(5, 40), label="n_ops")):
        op = data.draw(st.sampled_from(
            ["alloc", "free", "fork", "cow_write", "spill", "restore",
             "window_free"]), label="op")
        if op == "alloc":
            n = data.draw(st.integers(1, 4))
            if m.can_alloc(n):
                tables.append(m.alloc(n))
            else:
                with pytest.raises(NoFreeBlocks):
                    m.alloc(n)
        elif op == "free" and tables:
            t = tables.pop(data.draw(st.integers(0, len(tables) - 1)))
            m.free([b for b in t if b])
        elif op == "fork" and tables:
            t = tables[data.draw(st.integers(0, len(tables) - 1))]
            tables.append(m.fork(t))
        elif op == "cow_write" and tables:
            ti = data.draw(st.integers(0, len(tables) - 1))
            t = tables[ti]
            live = [i for i, b in enumerate(t) if b]
            if live:
                idx = data.draw(st.sampled_from(live))
                was = t[idx]
                if m.can_alloc(1) or not m.is_shared(was):
                    copies = []
                    new_t, wb = m.ensure_writable(
                        list(t), idx, lambda s, d: copies.append((s, d)))
                    tables[ti] = new_t
                    assert m.refcount(wb) >= 1
                    if was != wb:                # fault: copied + repointed
                        assert copies == [(was, wb)]
                        assert not m.is_shared(wb)
        elif op == "spill" and tables:
            t = tables.pop(data.draw(st.integers(0, len(tables) - 1)))
            # spilling a CoW-shared page would strand the other owner's
            # refcount; the runtime only spills exclusively-owned tables
            if any(m.is_shared(b) for b in t):
                m.free([b for b in t if b])
            else:
                key = ("req", len(spilled))
                m.spill(key, t, lambda bids: {"pages": jnp.zeros(
                    (1, len(bids), 2))})
                spilled[key] = len([b for b in t if b])
        elif op == "restore" and spilled:
            key = next(iter(spilled))
            n = spilled[key]
            if m.can_alloc(n):
                got = m.restore(key, lambda pages, bids: None)
                assert len(got) == n
                del spilled[key]
                tables.append(got)
            else:
                with pytest.raises(NoFreeBlocks):
                    m.restore(key, lambda pages, bids: None)
                assert m.spilled(key)            # archive entry intact
        elif op == "window_free" and tables:
            # free a prefix, as the scheduler's window freeing does
            ti = data.draw(st.integers(0, len(tables) - 1))
            t = tables[ti]
            k = data.draw(st.integers(0, len(t)))
            for i in range(k):
                if t[i]:
                    m.free([t[i]])
                    t[i] = 0
            # freeing never touches blocks past the prefix
            for b in t[k:]:
                assert b == 0 or m.refcount(b) >= 1
        check()
