"""Continuous-batching scheduler: admission, interleave, preemption order."""
from typing import List


from repro.serve.paged_kv import BlockManager, PagedKVConfig
from repro.serve.scheduler import (ContinuousScheduler, RequestState,
                                   SchedulerConfig)


def make_sched(num_blocks=16, block_size=4, max_slots=2, max_queue=8,
               prefill_chunk=4, chunks_per_step=1, watermark=1, **cb):
    pcfg = PagedKVConfig(block_size=block_size, num_blocks=num_blocks,
                         max_blocks_per_req=8)
    blocks = BlockManager(pcfg)
    clock = iter(range(10_000))
    sched = ContinuousScheduler(
        SchedulerConfig(max_slots=max_slots, max_queue=max_queue,
                        prefill_chunk=prefill_chunk,
                        prefill_chunks_per_step=chunks_per_step,
                        watermark_blocks=watermark),
        blocks, block_size, pcfg.max_blocks_per_req,
        clock=lambda: next(clock), **cb)
    return sched, blocks


def drive_prefill(sched, plan, first_token=7):
    """Simulate the runtime executing the planned prefill chunks."""
    for req in plan.prefill:
        n = min(sched.cfg.prefill_chunk, req.prompt_len - req.prefill_done)
        sched.on_prefill_chunk(req, n)
        if req.prefill_done == req.prompt_len:
            sched.on_prompt_complete(req, first_token)


def test_fcfs_admission_respects_slots():
    sched, _ = make_sched(max_slots=2)
    r = [sched.submit([1] * 4, 4) for _ in range(3)]
    plan = sched.schedule()
    assert [q.rid for q in plan.admitted] == [r[0].rid, r[1].rid]
    assert r[2].state is RequestState.QUEUED         # no slot left
    assert r[0].slot != r[1].slot
    assert all(q.state is RequestState.PREFILLING for q in plan.admitted)


def test_admission_control_rejects():
    sched, _ = make_sched(max_queue=2)
    assert sched.submit([], 4).state is RequestState.REJECTED   # empty
    big = sched.submit([1] * 100, 4)                 # exceeds table width
    assert big.state is RequestState.REJECTED
    sched.submit([1] * 4, 4)
    sched.submit([1] * 4, 4)
    overflow = sched.submit([1] * 4, 4)              # queue bound
    assert overflow.state is RequestState.REJECTED
    assert sched.counters["rejected"] == 3


def test_chunked_prefill_budget_and_interleave():
    # prompt of 8 with chunk 4 -> two prefill steps; decode of an already-
    # running request is scheduled in the SAME iterations (no starvation)
    sched, _ = make_sched(max_slots=2, prefill_chunk=4, chunks_per_step=1)
    fast = sched.submit([1] * 4, 8)
    plan = sched.schedule()
    drive_prefill(sched, plan)                       # fast fully prefilled
    assert fast.state is RequestState.RUNNING
    slow = sched.submit([2] * 8, 4)
    seen_decode_during_prefill = 0
    for _ in range(2):
        plan = sched.schedule()
        assert len(plan.prefill) <= 1                # budget respected
        if slow in plan.prefill and fast in plan.decode:
            seen_decode_during_prefill += 1
        drive_prefill(sched, plan)
        for req in plan.decode:
            sched.on_decode_token(req, 5)
    assert seen_decode_during_prefill == 2           # interleaved, not starved
    assert slow.state is RequestState.RUNNING


def test_decode_allocates_growth_block():
    sched, blocks = make_sched(block_size=4)
    req = sched.submit([1] * 4, 8)                   # 1 block prompt
    plan = sched.schedule()
    drive_prefill(sched, plan)
    assert len(req.table) == 1
    for i in range(4):                               # generate to pos 4..7
        plan = sched.schedule()
        for r in plan.decode:
            sched.on_decode_token(r, 5)
    assert len(req.table) == 2                       # grew exactly one block


def test_preemption_picks_youngest_and_resumes_fcfs():
    spilled: List[int] = []
    restored: List[int] = []

    def spill(req):
        spilled.append(req.rid)
        req_blocks = [b for b in req.table if b]
        sched.blocks.free(req_blocks)

    def restore(req):
        restored.append(req.rid)
        return sched.blocks.alloc(req.spilled_blocks)

    # 7 usable blocks, bs=2: two requests of prompt 4 (2 blocks each) that
    # each want 6 more tokens -> combined demand exceeds the pool
    sched, blocks = make_sched(num_blocks=8, block_size=2, max_slots=2,
                               watermark=1, spill=spill, restore=restore)
    old = sched.submit([1] * 4, 6, arrival=0.0)
    young = sched.submit([2] * 4, 6, arrival=1.0)
    for _ in range(2):                               # one chunk budget/step
        drive_prefill(sched, sched.schedule())
    assert {old.state, young.state} == {RequestState.RUNNING}

    preempted_at = None
    for i in range(16):
        plan = sched.schedule()
        if plan.preempted:
            preempted_at = i
            assert plan.preempted == [young]         # youngest loses its seat
            assert young.state is RequestState.PREEMPTED
            assert sched.queue[0] is young           # parked at queue front
        for r in plan.decode:
            sched.on_decode_token(r, 5)
        if old.done and young.done:
            break
    assert preempted_at is not None
    assert spilled == [young.rid]
    assert restored == [young.rid]                   # resumed via page restore
    assert old.state is RequestState.FINISHED
    assert young.state is RequestState.FINISHED
    assert len(old.generated) == 6 and len(young.generated) == 6
    assert sched.counters["preemptions"] == 1


def test_cancel_releases_blocks_and_slot():
    sched, blocks = make_sched()
    req = sched.submit([1] * 8, 4)
    sched.schedule()
    assert blocks.num_free < blocks.num_total
    assert sched.cancel(req.rid)
    assert req.state is RequestState.CANCELLED
    assert blocks.num_free == blocks.num_total
    assert not sched.cancel(req.rid)                 # idempotent
    # the freed slot is reusable immediately
    nxt = sched.submit([1] * 4, 4)
    plan = sched.schedule()
    assert nxt in plan.admitted


def test_cancel_queued_request_releases_forked_prefix_blocks():
    """A request can hold CoW-forked blocks while still queued (prefix hit
    followed by admission failure); cancel must drop those refs."""
    sched, blocks = make_sched(num_blocks=8, block_size=4, max_slots=1)
    cached = blocks.alloc(1)
    sched._prefix = lambda req: blocks.fork(cached)
    blocks.alloc(5)                                  # leave only 1 free
    req = sched.submit([1] * 12, 4)                  # needs 2 more + watermark
    sched.schedule()
    assert req.state is RequestState.QUEUED
    assert req.shared_blocks == 1
    assert blocks.refcount(cached[0]) == 2           # fork happened
    assert sched.cancel(req.rid)
    assert blocks.refcount(cached[0]) == 1           # fork released
    assert req.table == []


def test_eos_finishes_early():
    sched, _ = make_sched()
    req = sched.submit([1] * 4, 20, eos_id=9)
    plan = sched.schedule()
    drive_prefill(sched, plan)
    plan = sched.schedule()
    sched.on_decode_token(req, 9)                    # eos
    assert req.state is RequestState.FINISHED
    assert len(req.generated) == 2


def test_stats_shape():
    sched, _ = make_sched()
    sched.submit([1] * 4, 4)
    st = sched.stats()
    for key in ("queued", "running", "prefilling", "finished",
                "block_occupancy", "free_blocks", "preemptions"):
        assert key in st


# ---------------------------------------------------------------------------
# sliding-window block freeing (windowed StateSpec, mixer registry)
# ---------------------------------------------------------------------------
def make_windowed_sched(window, num_blocks=32, block_size=4, **kw):
    return make_sched(num_blocks=num_blocks, block_size=block_size,
                      free_window=window, **kw)


def test_window_freeing_frees_out_of_window_prefix():
    sched, blocks = make_windowed_sched(window=8, max_slots=1)
    req = sched.submit([1] * 16, 8)
    plan = sched.schedule()
    assert len(req.table) == 4                       # 16 tokens / bs 4
    drive_prefill(sched, plan)                       # chunk 1: done=4, no free
    for _ in range(3):
        drive_prefill(sched, sched.schedule())
    # prefill_done=16, cutoff=16+1-8 -> 2 blocks wholly below the window
    assert req.table[0] == 0 and req.table[1] == 0
    assert req.table[2] != 0 and req.table[3] != 0
    assert req.live_blocks == 2


def test_window_freeing_bound_and_liveness():
    """Live blocks never exceed ceil(window/bs)+1 during decode and never
    include a block a future query still needs."""
    window, bs = 8, 4
    bound = -(-window // bs) + 1
    sched, blocks = make_windowed_sched(window=window, block_size=bs,
                                        num_blocks=64, max_slots=1)
    req = sched.submit([1] * 8, 24)
    drive_prefill(sched, sched.schedule())
    drive_prefill(sched, sched.schedule())
    while req.state is RequestState.RUNNING:
        sched.schedule()
        sched.on_decode_token(req, 5)
        if req.state is not RequestState.RUNNING:
            break
        assert req.live_blocks <= bound, (req.total_len, req.table)
        # every in-window position still has a live block
        lo = max(0, req.total_len - 1 + 1 - window)
        for j in range(lo // bs, (req.total_len - 1) // bs + 1):
            if j < len(req.table):
                assert req.table[j] != 0, (j, req.table)


def test_window_freed_preempt_restore_keeps_alignment():
    """Preempting a windowed request archives only live blocks; restore
    rebuilds the table with the freed prefix re-nulled."""
    spilled = {}

    def spill(req):
        spilled[req.rid] = [b for b in req.table if b]

    def restore(req):
        blocks = spilled.pop(req.rid)
        return [0] * req.null_prefix + sched.blocks.alloc(len(blocks))

    sched, blocks = make_sched(num_blocks=16, block_size=4, max_slots=1,
                               free_window=8, spill=spill, restore=restore)
    req = sched.submit([1] * 16, 8)
    for _ in range(4):
        drive_prefill(sched, sched.schedule())
    assert req.null_prefix == 0 and req.table[:2] == [0, 0]
    # schedule() extends the table for the pending decode write (17 tokens
    # -> 5 table entries, 3 live) before the forced preemption
    sched._preempt(req, sched.schedule())
    assert req.null_prefix == 2 and req.spilled_blocks == 3
    free_before = blocks.num_free
    plan = sched.schedule()                          # resumes from the queue
    assert req in plan.resumed
    assert req.table[:2] == [0, 0] and req.live_blocks == 3
    assert blocks.num_free == free_before - 3


def test_restore_callback_runs_with_seat_assigned():
    """The runtime re-seats dense slot-state rows inside the restore
    callback, so the scheduler must assign req.slot BEFORE invoking it —
    otherwise a same-cycle re-preemption would spill the seat's stale
    rows over the good archive entry."""
    seats = []

    def restore(req):
        seats.append(req.slot)
        return sched.blocks.alloc(req.spilled_blocks)

    sched, blocks = make_sched(num_blocks=16, max_slots=1, restore=restore)
    req = sched.submit([1] * 8, 8)
    drive_prefill(sched, sched.schedule())
    drive_prefill(sched, sched.schedule())
    sched._preempt(req, sched.schedule())
    plan = sched.schedule()
    assert req in plan.resumed
    assert seats == [req.slot] and req.slot >= 0


def test_restore_failure_returns_the_seat():
    """A NoFreeBlocks during restore must hand the popped seat back."""
    from repro.serve.paged_kv import NoFreeBlocks

    def restore(req):
        raise NoFreeBlocks("archive cannot re-seat yet")

    sched, blocks = make_sched(num_blocks=16, max_slots=2, restore=restore)
    req = sched.submit([1] * 8, 8)
    drive_prefill(sched, sched.schedule())
    drive_prefill(sched, sched.schedule())
    sched._preempt(req, sched.schedule())
    sched.schedule()                          # resume attempt fails
    assert req.state is RequestState.PREEMPTED and req.slot == -1
    assert len(sched._free_slots) == 2        # seat not leaked


def test_inter_token_latency_is_the_gap_between_a_requests_tokens():
    sched, _ = make_sched(max_slots=2, chunks_per_step=2)
    a, b = sched.submit([1] * 4, 4), sched.submit([2] * 4, 4)
    drive_prefill(sched, sched.schedule())
    gaps, last = [], {r.rid: r.t_first_token for r in (a, b)}
    sched.schedule()
    for req, tok in ((a, 5), (b, 5), (a, 6)):
        sched.on_decode_token(req, tok)     # each clock read is one tick
        gaps.append(req.t_last_token - last[req.rid])
        last[req.rid] = req.t_last_token
    itl = sched.obs.metrics.histogram("serve.itl_s")
    # one gap per token after the first, each its request's own
    assert (itl.count, itl.sum, itl.min, itl.max) == (
        3, sum(gaps), min(gaps), max(gaps))
    # first tokens at ticks 4 and 5, then tokens at 6, 7 and 8
    assert gaps == [2, 2, 2]


def test_prefill_steps_count_engine_steps_from_admission_to_first_token():
    # one chunk per step: a 10-token prompt takes three steps, the 4-token
    # prompt admitted beside it waits for them and prefills in the fourth
    sched, _ = make_sched(max_slots=2, chunks_per_step=1)
    a, b = sched.submit([1] * 10, 4), sched.submit([2] * 4, 4)
    for _ in range(4):
        drive_prefill(sched, sched.schedule())
    assert (a.prefill_steps, b.prefill_steps) == (3, 4)
    late = sched.submit([3] * 4, 4)         # queued until a seat frees
    assert late.prefill_steps is None
