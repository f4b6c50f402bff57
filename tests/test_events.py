"""Event-driven runtime API: queue priority dispatch, policy-table
completeness, the formal ExecutionBackend contract, streaming-vs-run()
parity on both backends, and the on-device logprob plane (one transfer
per page, extended from the test_decode_fused spy pattern)."""
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core import plan as plan_lib
from repro.core.backend import ExecutionBackend, validate_backend
from repro.core.events import (EventKind, EventQueue, SeqFinishedEvent,
                               TokenBlockEvent)
from repro.core.scheduler import (CoroutineScheduler, SchedulerConfig,
                                  SchedulerPolicy)
from repro.runtime.api import BatchMaster, BatchRequest
from repro.runtime.cluster import SimEngine
from repro.runtime.engine import NodeEngine
from repro.sampling import SamplingParams


# ---------------------------------------------------------------------------
# event queue + policy table
# ---------------------------------------------------------------------------


def test_event_queue_priority_order_under_contention():
    """SYNC (correctness) pops before REFILL (utilization) pops before
    MIGRATE (opportunistic), regardless of push order; equal priority is
    FIFO."""
    q = EventQueue()
    q.push(EventKind.MIGRATE)
    q.push(EventKind.REFILL, node=1)
    q.push(EventKind.SYNC)
    q.push(EventKind.REFILL, node=2)
    popped = [q.pop() for _ in range(4)]
    assert [e.kind for e in popped] == [EventKind.SYNC, EventKind.REFILL,
                                        EventKind.REFILL, EventKind.MIGRATE]
    assert [e.node for e in popped if e.kind == EventKind.REFILL] == [1, 2]
    assert q.pop() is None


def test_scheduler_drains_queue_in_priority_order():
    """One scheduler round on a live node dispatches refill -> decode ->
    sync -> sync_drain -> evict -> extend -> refill -> longtail, sequenced
    purely by the queue's EventKind priorities (no inline phase calls)."""
    order = []

    def wrap(label, fn):
        def h(sched, ev):
            order.append(label)
            fn(sched, ev)
        return h

    base = SchedulerPolicy()
    pol = SchedulerPolicy(
        sync=wrap("sync", base.sync),
        sync_drain=wrap("sync_drain", base.sync_drain),
        seq_done=wrap("seq_done", base.seq_done),
        page_boundary=wrap("page_boundary", base.page_boundary),
        module_ready=wrap("module_ready", base.module_ready),
        refill=wrap("refill", base.refill),
        long_tail=wrap("long_tail", base.long_tail),
        migrate=wrap("migrate", base.migrate),
        node_failure=wrap("node_failure", base.node_failure))
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=2, max_len=64, page_size=8, seed=0)
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8),
                               policy=pol)
    sched.submit([[2, 3, 4]] * 2, [10] * 2)
    sched.step()
    assert order == ["refill", "module_ready", "sync", "sync_drain",
                     "seq_done", "page_boundary", "refill", "long_tail"]


def test_every_eventkind_has_a_default_handler():
    """No orphan event kinds: the default policy registers a handler for
    every EventKind member (the queue was dead code before this table)."""
    table = SchedulerPolicy().table()
    assert set(table) == set(EventKind)
    assert all(callable(h) for h in table.values())


# ---------------------------------------------------------------------------
# ExecutionBackend contract
# ---------------------------------------------------------------------------


def test_validate_backend_rejects_missing_members():
    class Bogus:
        node_id = 0
        max_active = 1

        def clock(self):
            return 0.0

    with pytest.raises(TypeError, match="decode_page"):
        validate_backend(Bogus())
    with pytest.raises(TypeError):
        CoroutineScheduler([Bogus()])


def test_engines_conform_to_backend_protocol():
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=2, max_len=64, page_size=8)
    assert validate_backend(eng) is eng
    assert isinstance(eng, ExecutionBackend)
    sim = SimEngine(get_config("llama3_2_1b"), plan_lib.Hardware(),
                    max_active=8, max_len=1024)
    assert validate_backend(sim) is sim
    assert isinstance(sim, ExecutionBackend)


# ---------------------------------------------------------------------------
# streaming-vs-run() parity
# ---------------------------------------------------------------------------


def _assert_stream_matches_run(make_sched, submit):
    """Two identical schedulers: run() on one, stream() on the other; the
    concatenated TokenBlockEvents must reproduce run()'s tokens exactly,
    with contiguous offsets and one SeqFinishedEvent per sequence."""
    s_run = make_sched()
    ids_run = submit(s_run)
    rep = s_run.run(max_ticks=500)
    assert rep["completed"] == len(ids_run)
    assert rep["status"] == "completed"

    s_str = make_sched()
    ids_str = submit(s_str)
    streamed = {i: [] for i in ids_str}
    finished = set()
    for rec in s_str.stream(max_ticks=500):
        if isinstance(rec, TokenBlockEvent):
            assert rec.offset == len(streamed[rec.seq_id])
            streamed[rec.seq_id] += rec.tokens
        elif isinstance(rec, SeqFinishedEvent):
            finished.add(rec.seq_id)
    assert finished == set(ids_str)
    for ir, is_ in zip(ids_run, ids_str):
        assert streamed[is_] == s_run.cos[ir].generated
        assert streamed[is_] == s_str.cos[is_].generated


def test_stream_matches_run_node_engine(rng):
    cfg = reduced_config("llama3_2_1b")
    prompts = [list(rng.integers(2, cfg.vocab_size, int(n)))
               for n in rng.integers(4, 10, 5)]
    max_out = [12, 5, 9, 16, 7]
    sps = [SamplingParams()] * 3 + [SamplingParams(temperature=0.9,
                                                   top_k=30, seed=7)] * 2

    def make():
        eng = NodeEngine(cfg, max_active=3, max_len=128, page_size=8,
                         seed=0)
        return CoroutineScheduler([eng], SchedulerConfig(page_size=8))

    _assert_stream_matches_run(
        make, lambda s: s.submit(prompts, max_out, sampling=sps))


def test_stream_matches_run_sim_engine():
    cfg = get_config("llama3_2_1b")
    hw = plan_lib.Hardware()
    plan = plan_lib.search_plan(cfg, hw, ctx=512, new_tokens=1,
                                max_active=16)
    prompts = [[1] * 32] * 8
    max_out = [40, 12, 25, 60, 8, 33, 17, 50]
    sp = SamplingParams(temperature=1.0, seed=5)   # pseudo-stream tokens

    def make():
        engines = [SimEngine(cfg, hw, node_id=i, max_active=4,
                             max_len=1024, page_size=16, plan=plan)
                   for i in range(2)]
        return CoroutineScheduler(engines, SchedulerConfig(page_size=16))

    _assert_stream_matches_run(
        make, lambda s: s.submit(prompts, max_out, sampling=sp,
                                 logprobs=True))


def test_abandoned_stream_never_reports_completed():
    """Breaking out of stream() must not leave a normal-looking report:
    status is derived from live sequence state, not from loop exit."""
    cfg = get_config("llama3_2_1b")
    eng = SimEngine(cfg, plan_lib.Hardware(), max_active=4, max_len=4096,
                    page_size=16)
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=16))
    sched.submit([[1] * 16] * 2, [500] * 2)
    for _ in sched.stream():
        break                      # consumer abandons the stream
    rep = sched.report()
    assert rep["status"] == "exhausted"
    assert rep["completed"] < rep["total"]


def test_run_reports_exhausted_status(caplog):
    cfg = get_config("llama3_2_1b")
    hw = plan_lib.Hardware()
    eng = SimEngine(cfg, hw, max_active=4, max_len=4096, page_size=16)
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=16))
    sched.submit([[1] * 16] * 2, [500] * 2)
    with caplog.at_level("WARNING"):
        rep = sched.run(max_ticks=2)
    assert rep["status"] == "exhausted"
    assert rep["completed"] < rep["total"]
    assert any("exhausted" in r.message for r in caplog.records)
    # finishing the batch flips the status back
    rep = sched.run(max_ticks=10000)
    assert rep["status"] == "completed"
    assert rep["completed"] == rep["total"]


# ---------------------------------------------------------------------------
# logprobs plane: one transfer per page (extended spy pattern)
# ---------------------------------------------------------------------------


def test_logprobs_one_transfer_per_decode_page():
    """Transfer-spy: requesting logprobs + top-logprobs must NOT add any
    device->host transfer — the (P, B) logprob plane rides the page's one
    packed block."""
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=128, page_size=8, seed=0)
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
    ids = sched.submit([[2, 3, 4, 5]] * 3, [20] * 3, logprobs=True,
                       top_logprobs=2)

    calls = []
    in_page = [False]
    orig_decode, orig_to_host = eng.decode_page, eng._to_host

    def spy_to_host(arr):
        if in_page[0]:              # ignore prefill/sync transfers
            calls[-1] += 1
        return orig_to_host(arr)

    def spy_decode(active, P):
        calls.append(0)
        in_page[0] = True
        try:
            return orig_decode(active, P)
        finally:
            in_page[0] = False

    eng.decode_page, eng._to_host = spy_decode, spy_to_host
    rep = sched.run(max_ticks=300)
    assert rep["completed"] == 3
    assert calls and all(c == 1 for c in calls), calls
    for i in ids:
        co = sched.cos[i]
        assert len(co.token_logprobs) == len(co.generated) == 20
        assert len(co.top_token_logprobs) == 20
        assert all(len(row) == 2 for row in co.top_token_logprobs)
        # greedy: the top-1 alternative IS the chosen token
        for tok, lp, row in zip(co.generated, co.token_logprobs,
                                co.top_token_logprobs):
            assert row[0][0] == tok
            np.testing.assert_allclose(row[0][1], lp, atol=1e-5)
        assert all(lp <= 1e-6 for lp in co.token_logprobs)


def test_logprobs_fused_matches_looped(rng):
    """The on-device logprob plane agrees with the host-side looped
    baseline (same tokens, logprobs equal to float32 tolerance)."""
    cfg = reduced_config("llama3_2_1b")
    prompts = [list(rng.integers(2, cfg.vocab_size, int(n)))
               for n in rng.integers(4, 10, 3)]

    def run(fused):
        eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8,
                         seed=0, fused=fused)
        sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
        ids = sched.submit(prompts, [9, 6, 12], logprobs=True,
                           top_logprobs=3)
        rep = sched.run(max_ticks=200)
        assert rep["completed"] == 3
        return [sched.cos[i] for i in ids]

    f, l = run(True), run(False)
    assert [c.generated for c in f] == [c.generated for c in l]
    for a, b in zip(f, l):
        np.testing.assert_allclose(a.token_logprobs, b.token_logprobs,
                                   atol=2e-4)
        for ra, rb in zip(a.top_token_logprobs, b.top_token_logprobs):
            assert [t for t, _ in ra] == [t for t, _ in rb]
            np.testing.assert_allclose([x for _, x in ra],
                                       [x for _, x in rb], atol=2e-4)


def test_logprob_plane_is_int32():
    """The plane is int32 with the log-probs bitcast into it: token ids
    bitcast into an f32 plane are denormals, which the TPU flushes to
    zero, so every id would come back as 0 there."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 1000))
    tokens = jnp.asarray([3, 999, 500, 7], jnp.int32)
    block = T.pack_logprob_block(tokens, logits, 3)
    assert block.dtype == jnp.int32
    toks, chosen, vals, ids = T.unpack_logprob_block(np.asarray(block)[None])
    lp = jax.nn.log_softmax(logits, axis=-1)
    ref_vals, ref_ids = jax.lax.top_k(lp, 3)
    np.testing.assert_array_equal(toks[0], tokens)
    np.testing.assert_array_equal(ids[0], ref_ids)
    np.testing.assert_array_equal(vals[0], ref_vals)
    np.testing.assert_array_equal(chosen[0], lp[jnp.arange(4), tokens])


def test_logprobs_do_not_perturb_token_stream(rng):
    cfg = reduced_config("llama3_2_1b")
    prompts = [list(rng.integers(2, cfg.vocab_size, 5)) for _ in range(3)]
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=11)

    def run(lp):
        eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8, seed=0)
        sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
        ids = sched.submit(prompts, [10] * 3, sampling=sp, logprobs=lp)
        assert sched.run(max_ticks=200)["completed"] == 3
        return [sched.cos[i].generated for i in ids]

    assert run(False) == run(True)


# ---------------------------------------------------------------------------
# batch API: stream-first results + logprobs surface
# ---------------------------------------------------------------------------


def test_batch_master_stream_first(rng):
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8)
    master = BatchMaster([eng], SchedulerConfig(page_size=8))
    reqs = [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, 100, 5)),
                         max_tokens=4 + i, logprobs=True,
                         top_logprobs=2 if i % 2 else 0)
            for i in range(5)]
    bid = master.submit(reqs)
    streamed: dict = {r.custom_id: [] for r in reqs}
    seen_partial = False
    bo = master.batches[bid]
    for rec in master.stream(bid):
        assert rec.custom_id in streamed
        if isinstance(rec, TokenBlockEvent):
            streamed[rec.custom_id] += rec.tokens
            if rec.logprobs is not None:
                assert len(rec.logprobs) == len(rec.tokens)
        if isinstance(rec, SeqFinishedEvent):
            seen_partial = seen_partial or (
                0 < len(bo.results) < len(reqs))
    assert seen_partial, "results must fill incrementally during the stream"
    assert bo.status == "completed"
    assert [r["custom_id"] for r in bo.results] == [f"r{i}" for i in range(5)]
    for i, row in enumerate(bo.results):
        assert row["response"]["tokens"] == streamed[f"r{i}"]
        assert len(row["response"]["tokens"]) == reqs[i].max_tokens
        lp = row["response"]["logprobs"]
        assert len(lp["token_logprobs"]) == reqs[i].max_tokens
        if reqs[i].top_logprobs:
            assert all(len(t) == 2 for t in lp["top_logprobs"][0:1])
    assert bo.request_counts == {"total": 5, "completed": 5, "failed": 0}


def test_batch_master_duplicate_custom_ids_kept_separate(rng):
    """Result rows are keyed by seq_id internally, so two requests sharing
    a custom_id each keep their own output."""
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8)
    master = BatchMaster([eng], SchedulerConfig(page_size=8))
    reqs = [BatchRequest(custom_id="dup", prompt=[2, 3, 4, 5],
                         max_tokens=4),
            BatchRequest(custom_id="dup", prompt=[6, 7, 8],
                         max_tokens=6)]
    bid = master.submit(reqs)
    bo = master.run(bid)
    assert [len(r["response"]["tokens"]) for r in bo.results] == [4, 6]
    # per-batch working state (scheduler + coroutines) is released
    assert bid not in master._scheds and bid not in master._rows


def test_batch_master_abandoned_stream_then_rerun(rng):
    """Abandoning stream() mid-flight must not corrupt a later run():
    the fresh pass resets results/counts, and run() on a finalized batch
    is idempotent (no KeyError, no re-decode)."""
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8)
    master = BatchMaster([eng], SchedulerConfig(page_size=8))
    reqs = [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, 100, 4)),
                         max_tokens=3 + i) for i in range(4)]
    bid = master.submit(reqs)
    for rec in master.stream(bid):
        if isinstance(rec, SeqFinishedEvent):
            break                      # client disconnects mid-batch
    assert master.batches[bid].status == "in_progress"
    bo = master.run(bid)               # recovery pass
    assert bo.status == "completed"
    assert bo.request_counts == {"total": 4, "completed": 4, "failed": 0}
    assert [r["custom_id"] for r in bo.results] == [f"r{i}" for i in range(4)]
    bo2 = master.run(bid)              # idempotent on finalized batch
    assert bo2 is bo
    with pytest.raises(ValueError, match="finalized"):
        next(iter(master.stream(bid)))


def test_looped_module_granularity_logprobs(rng):
    """fused=False + module_granularity: logprobs must stay aligned with
    the generated stream (the baseline path computes them host-side)."""
    cfg = reduced_config("phi3_5_moe")
    prompts = [list(rng.integers(2, cfg.vocab_size, 5)) for _ in range(2)]

    def run(fused):
        eng = NodeEngine(cfg, max_active=2, max_len=64, page_size=8,
                         seed=0, fused=fused, module_granularity=True,
                         b_attn=1)
        sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
        ids = sched.submit(prompts, [7, 5], logprobs=True)
        assert sched.run(max_ticks=200)["completed"] == 2
        return [sched.cos[i] for i in ids]

    f, l = run(True), run(False)
    assert [c.generated for c in f] == [c.generated for c in l]
    for a, b in zip(f, l):
        assert len(a.token_logprobs) == len(a.generated)
        assert len(b.token_logprobs) == len(b.generated)
        np.testing.assert_allclose(a.token_logprobs, b.token_logprobs,
                                   atol=2e-4)


# ---------------------------------------------------------------------------
# NODE_FAILURE event through the default policy
# ---------------------------------------------------------------------------


def test_node_failure_event_recovers_sequences():
    cfg = reduced_config("llama3_2_1b")
    engs = [NodeEngine(cfg, node_id=i, max_active=3, max_len=64,
                       page_size=8, seed=0) for i in range(2)]
    sched = CoroutineScheduler(engs, SchedulerConfig(page_size=8))
    ids = sched.submit([[2, 3, 4]] * 6, [10] * 6)
    sched.step()                       # both nodes prefill + first page
    sched.queue.push(EventKind.NODE_FAILURE, node=0)
    rep = sched.run(max_ticks=300)
    assert rep["completed"] == 6, "all sequences survive the failure"
    assert [e.node_id for e in sched.engines] == [1]
    assert all(sched.cos[i].done for i in ids)
    # everything that was still in flight at failure time moved to node 1
    # (sequences already finished on node 0 keep their historical placement)
    assert any(sched.cos[i].node == 1 for i in ids)
