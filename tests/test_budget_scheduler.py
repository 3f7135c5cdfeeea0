"""Token-budget (sarathi_serve) scheduler invariants — property tests via
the _prop shim (real hypothesis when installed, bounded fallback otherwise),
driven with a fake token feeder; no model execution."""
from _prop import given, settings, strategies as st

from repro.scheduler import POLICIES, Request, SarathiServeScheduler
from repro.scheduler.request import State


def drive(sched, reqs, record, now=None):
    for r in reqs:
        sched.submit(r)
    guard = 0
    while sched.has_work:
        n_decoding = sum(1 for r in sched.running
                         if r.state == State.DECODING)
        kw = {"now": now} if now is not None else {}
        plan = sched.next_plan(**kw)
        if plan is None:
            break
        record(plan, n_decoding)
        tokens = {}
        for c in plan.chunks:
            if c.is_last:
                tokens[c.req_id] = 1
        for d in plan.decodes:
            tokens[d.req_id] = 1
        sched.on_tokens(tokens)
        guard += 1
        assert guard < 100_000, "scheduler failed to make progress"


def make_sched(chunk, slots, budget, **kw):
    return SarathiServeScheduler(n_slots=slots,
                                 max_decodes=max(slots - 1, 1),
                                 chunk_size=chunk, token_budget=budget, **kw)


def test_registered_in_policies():
    assert POLICIES["sarathi_serve"] is SarathiServeScheduler


@settings(deadline=None, max_examples=40)
@given(
    prompts=st.lists(st.integers(1, 90), min_size=1, max_size=12),
    decode_len=st.integers(1, 9),
    chunk=st.integers(1, 33),
    slots=st.integers(1, 6),
    budget=st.integers(1, 64),
)
def test_budget_invariants(prompts, decode_len, chunk, slots, budget):
    reqs = [Request(prompt=[1] * p, max_new_tokens=decode_len)
            for p in prompts]
    sched = make_sched(chunk, slots, budget)
    max_dec = max(slots - 1, 1)
    prefill_seen = {r.req_id: [] for r in reqs}

    def rec(plan, n_decoding):
        # 1) the budget is a hard per-iteration cap
        assert plan.n_prefill_tokens + plan.n_decode_tokens <= budget
        # 2) decodes first, never evicted for prefill: every iteration
        #    schedules as many decodes as are runnable under the caps,
        #    regardless of how much prefill work is waiting
        assert plan.n_decode_tokens == min(n_decoding, max_dec, budget)
        # 3) every chunk respects the chunk size and slot bookkeeping
        for c in plan.chunks:
            assert 1 <= len(c.tokens) <= chunk
            prefill_seen[c.req_id].append((c.start, len(c.tokens)))
        ids = [c.req_id for c in plan.chunks]
        assert len(ids) == len(set(ids))       # one chunk per request
        dec_ids = [d.req_id for d in plan.decodes]
        assert len(dec_ids) == len(set(dec_ids))
        assert not set(ids) & set(dec_ids)     # no self-piggyback

    drive(sched, reqs, rec)
    # 4) no starvation: every request fully prefilled (chunks partition the
    #    prompt exactly) and fully decoded
    for r in reqs:
        segs = prefill_seen[r.req_id]
        total = 0
        for (s, n) in segs:
            assert s == total
            total += n
        assert total == r.prompt_len
        assert len(r.output) == decode_len
        assert r.done


@settings(deadline=None, max_examples=25)
@given(prompts=st.lists(st.integers(1, 50), min_size=2, max_size=10),
       chunk=st.integers(1, 16), budget=st.integers(4, 48))
def test_multi_chunk_fills_budget(prompts, chunk, budget):
    """With no decodes yet and several waiting prompts, the first iteration
    packs chunks from multiple requests until the budget (or the admitted
    work) runs out."""
    reqs = [Request(prompt=[1] * p, max_new_tokens=1) for p in prompts]
    sched = make_sched(chunk, len(prompts) + 1, budget)
    for r in reqs:
        sched.submit(r)
    plan = sched.next_plan()
    assert plan is not None and not plan.decodes
    # greedy FCFS packing, one chunk (<= chunk_size) per request, until the
    # budget truncates
    assert plan.n_prefill_tokens == \
        min(budget, sum(min(chunk, p) for p in prompts))
    assert len(plan.chunks) >= 2 or budget <= min(chunk, prompts[0])


def test_arrival_time_gating_fcfs():
    a = Request(prompt=[1] * 4, max_new_tokens=2, arrival_time=0.0)
    b = Request(prompt=[1] * 4, max_new_tokens=2, arrival_time=5.0)
    sched = make_sched(chunk=4, slots=4, budget=8)
    sched.submit(a)
    sched.submit(b)
    plan = sched.next_plan(now=0.0)
    assert [c.req_id for c in plan.chunks] == [a.req_id]   # b not arrived
    plan = sched.next_plan(now=10.0)
    assert b.req_id in [c.req_id for c in plan.chunks]


def test_slot_pressure_backoff():
    """While the decode slots are saturated, new requests are NOT admitted;
    they are once a decode finishes."""
    a = Request(prompt=[1], max_new_tokens=2)
    b = Request(prompt=[1], max_new_tokens=6)
    new = Request(prompt=[1] * 8, max_new_tokens=1)
    sched = make_sched(chunk=8, slots=3, budget=16)     # max_decodes = 2
    sched.submit(a)
    sched.submit(b)
    sched.next_plan()                   # prefill both 1-token prompts
    sched.on_tokens({a.req_id: 1, b.req_id: 1})
    assert a.state == State.DECODING and b.state == State.DECODING
    sched.submit(new)
    plan = sched.next_plan()
    assert new.req_id not in [c.req_id for c in plan.chunks]  # backed off
    assert len(plan.decodes) == 2       # both decodes still served
    sched.on_tokens({a.req_id: 1, b.req_id: 1})
    assert a.done                       # a hit max_new_tokens=2
    plan = sched.next_plan()            # pressure released
    assert new.req_id in [c.req_id for c in plan.chunks]
    assert [d.req_id for d in plan.decodes] == [b.req_id]


def test_replay_matches_offline_sarathi_plans():
    """budget = C + D, one chunk per iteration, no backoff => plan-for-plan
    identical to the offline SarathiScheduler (the deterministic-replay
    guarantee the online loop builds on)."""
    from repro.scheduler import SarathiScheduler

    C, D, slots = 8, 3, 4
    mk = lambda: [Request(prompt=[1] * p, max_new_tokens=d, req_id=i)
                  for i, (p, d) in enumerate(
                      [(13, 6), (9, 4), (21, 5), (5, 7), (17, 3)])]
    ref_plans, got_plans = [], []
    ref = SarathiScheduler(n_slots=slots, max_decodes=D, chunk_size=C)
    drive(ref, mk(), lambda p, n: ref_plans.append(p))
    got = make_sched(C, slots, C + D, max_chunks_per_iter=1,
                     admit_backoff=False)
    drive(got, mk(), lambda p, n: got_plans.append(p))
    assert len(ref_plans) == len(got_plans)
    for a, b in zip(ref_plans, got_plans):
        assert [(c.req_id, c.start, list(c.tokens), c.is_last)
                for c in a.chunks] == \
            [(c.req_id, c.start, list(c.tokens), c.is_last)
             for c in b.chunks]
        assert [(d.req_id, d.ctx) for d in a.decodes] == \
            [(d.req_id, d.ctx) for d in b.decodes]


def test_all_decodes_fit_when_budget_covers_them():
    """Regression: the decode cap is computed against the FULL budget, not
    a per-decode-decremented one — with token_budget == max_decodes every
    decoding request gets its token each iteration."""
    sched = SarathiServeScheduler(n_slots=10, max_decodes=10,
                                  chunk_size=4, token_budget=10)
    for _ in range(10):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=5))
    # drive everything into DECODING
    while any(r.state != State.DECODING for r in sched.running) \
            or sched.waiting:
        plan = sched.next_plan()
        sched.on_tokens({c.req_id: 1 for c in plan.chunks if c.is_last})
    plan = sched.next_plan()
    assert len(plan.decodes) == 10


def test_block_aware_admission_rejects_never_fitting_prompt():
    """A prompt that can NEVER fit the pool (even drained) must be
    rejected, not wedge the FCFS queue in front of servable requests."""
    from repro.cache import BlockManager
    bm = BlockManager(11, 4, watermark=0.2)       # 10 usable, floor 2
    sched = SarathiServeScheduler(n_slots=4, max_decodes=3, chunk_size=8,
                                  token_budget=11, block_manager=bm)
    giant = Request(prompt=[1] * 33, max_new_tokens=2)   # 9 > 10 - 2 blocks
    small = Request(prompt=[1] * 8, max_new_tokens=2)
    recorded = []
    drive(sched, [giant, small], lambda plan, n: recorded.append(plan))
    assert giant in sched.rejected and giant.done and not giant.output
    assert small.done and len(small.output) == 2
    assert bm.n_used == 0


def test_preempted_request_readmits_past_watermark():
    """Appends ignore the watermark, so a preempted request may be larger
    than the fresh-admission threshold; readmission must use append
    semantics or the request starves after eviction."""
    from repro.cache import BlockManager
    bm = BlockManager(11, 4, watermark=0.2)       # floor 2 of 10 usable
    sched = SarathiServeScheduler(n_slots=2, max_decodes=1, chunk_size=40,
                                  token_budget=41, block_manager=bm)
    req = Request(prompt=[1] * 30, max_new_tokens=6)
    sched.submit(req)
    plan = sched.next_plan()
    assert plan is not None and plan.chunks          # admitted + prefilled
    sched.on_tokens({req.req_id: 1})
    # decode to ctx 34 then preempt: 34 tokens -> 9 blocks > 10 - 2
    for _ in range(3):
        plan = sched.next_plan()
        sched.on_tokens({d.req_id: 1 for d in plan.decodes})
    assert not req.done
    sched._preempt(req)
    assert req.n_preemptions == 1 and bm.n_used == 0
    # readmission bypasses the watermark (append semantics): finishes
    drive(sched, [], lambda plan, n: None)
    assert req.done and req not in sched.rejected
    assert len(req.output) == 6


def test_first_scheduled_stamped_at_first_chunk_and_kept_across_preemption():
    """``first_scheduled`` is the loop clock at the request's first chunk:
    not stamped while the request waits for its arrival, not moved by
    later chunks, and kept when a preemption re-queues the request."""
    from repro.cache import BlockManager
    bm = BlockManager(64, 4)
    sched = SarathiServeScheduler(n_slots=2, max_decodes=1, chunk_size=8,
                                  token_budget=9, block_manager=bm)
    req = Request(prompt=[1] * 20, max_new_tokens=4, arrival_time=1.0)
    sched.submit(req)
    assert sched.next_plan(now=0.5) is None           # not arrived yet
    assert req.first_scheduled is None
    plan = sched.next_plan(now=2.0)
    assert [c.start for c in plan.chunks] == [0]
    assert req.first_scheduled == 2.0
    plan = sched.next_plan(now=3.0)
    assert [c.start for c in plan.chunks] == [8]
    assert req.first_scheduled == 2.0
    sched._preempt(req)
    assert req.n_preemptions == 1 and req.prefilled == 0
    plan = sched.next_plan(now=5.0)                   # re-prefill from 0
    assert [c.start for c in plan.chunks] == [0]
    assert req.first_scheduled == 2.0


def test_concurrent_oversized_prefills_do_not_wedge_tiny_pool():
    """Regression for the admit-then-starve race: admission used to check
    the whole prompt against the INSTANTANEOUS free list, so two prompts
    of 6 blocks each both passed on an 8-block pool; their lazy per-chunk
    allocations then collided mid-prompt and — prefills never preempt —
    every subsequent plan came back empty (wedge).  The admission
    reservation makes the second prompt wait until the first one's
    earmarked blocks are actually released."""
    from repro.cache import BlockManager
    bm = BlockManager(9, 4)                     # 8 usable, no watermark
    sched = make_sched(chunk=4, slots=4, budget=8, block_manager=bm)
    a = Request(prompt=[1] * 24, max_new_tokens=2)    # 6 blocks
    b = Request(prompt=[1] * 24, max_new_tokens=2)    # 6 blocks
    sched.submit(a)
    sched.submit(b)
    plan = sched.next_plan()
    # only a admitted; its novel blocks are earmarked and b is held back
    assert [c.req_id for c in plan.chunks] == [a.req_id]
    assert bm.reserved_for(a.req_id) > 0
    assert b in sched.waiting
    drive(sched, [], lambda plan, n: None)      # would wedge pre-fix
    assert a.done and len(a.output) == 2
    assert b.done and len(b.output) == 2
    assert b not in sched.rejected
    assert bm.n_reserved == 0 and bm.n_used == 0
