"""Runtime tests: batch API, checkpoint/cold-start, failure recovery,
cluster simulation + elasticity, job ledger, plan optimizer."""
import json
import os

import numpy as np
import jax
import pytest

from repro.configs import get_config, reduced_config
from repro.core import plan as plan_lib
from repro.core.scheduler import CoroutineScheduler, SchedulerConfig
from repro.models import transformer as T
from repro.runtime import checkpoint as ckpt
from repro.runtime.api import BatchMaster, BatchRequest
from repro.runtime.cluster import (Cluster, SimEngine, fixed_workload,
                                   longtail_workload, run_static_baseline)
from repro.runtime.engine import NodeEngine
from repro.runtime.failure import HealthMonitor, Heartbeat, DeviceStatus, \
    recovery_choice
from repro.runtime.ledger import (JobLedger, LedgerError,
                                  SegmentedJobLedger, run_resumable)


def test_batch_api_order_and_completion(rng):
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8)
    master = BatchMaster([eng], SchedulerConfig(page_size=8))
    reqs = [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, 100, 5)),
                         max_tokens=int(rng.integers(2, 8)))
            for i in range(5)]
    bid = master.submit(reqs)
    bo = master.run(bid)
    assert bo.status == "completed"
    assert [r["custom_id"] for r in bo.results] == [f"r{i}" for i in range(5)]
    assert bo.request_counts["completed"] == 5
    assert all(len(r["response"]["tokens"]) == reqs[i].max_tokens
               for i, r in enumerate(bo.results))


def test_build_master_commits_each_engine_to_its_device(rng):
    """``build_master`` puts engine i on ``devices[i % len(devices)]`` and
    every array an engine owns stays committed there through a batch."""
    from repro.launch.serve import build_master
    cfg = reduced_config("qwen2_0_5b")
    devs = jax.devices()
    master, engines = build_master(cfg, nodes=2, max_active=2, max_len=64,
                                   page_size=8, devices=devs)
    reqs = [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, 100, 6)), max_tokens=5)
            for i in range(4)]
    assert master.run(master.submit(reqs)).request_counts["failed"] == 0
    for i, e in enumerate(engines):
        assert e.device == devs[i % len(devs)]
        owned = jax.tree.leaves((e.params, e.cache, e.tokens, e.lengths,
                                 e._sample_state))
        assert all(a.committed and a.devices() == {e.device} for a in owned)


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The cache goes to $JAX_COMPILATION_CACHE_DIR when it is set, else
    to one fixed path inside the checkout that git ignores."""
    from repro.launch import compile_cache
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = compile_cache.CHECKOUT_CACHE_DIR.parent
        assert compile_cache.cache_dir() == str(repo / ".jax_cache")
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
        assert compile_cache.cache_dir() == str(tmp_path / env)


def test_checkpoint_roundtrip(tmp_path, rng):
    cfg = reduced_config("qwen2_0_5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path / "c1"), params, extra={"step": 7})
    flat, extra = ckpt.restore(str(tmp_path / "c1"), mmap=True)
    assert extra["step"] == 7
    restored = ckpt.unflatten_into(params, flat)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pool_snapshot_restart(tmp_path, rng):
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=2, max_len=64, page_size=8)
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
    sched.submit([[2, 3, 4]] * 4, [6] * 4)
    # run a few ticks only (mid-batch), snapshot, restart fresh engine
    for _ in range(2):
        sched._node_tick(0, eng)
    ckpt.snapshot_pool(str(tmp_path / "pool"), sched)
    eng2 = NodeEngine(cfg, max_active=2, max_len=64, page_size=8)
    sched2 = CoroutineScheduler([eng2], SchedulerConfig(page_size=8))
    n = ckpt.restore_pool(str(tmp_path / "pool"), sched2)
    assert n == 4
    rep = sched2.run(max_ticks=300)
    assert rep["completed"] == 4


def test_health_monitor_detects_failure():
    hm = HealthMonitor(nodes=3, interval_s=1.0, dead_after=3)
    failures = []
    hm.on_failure = failures.append
    for t in range(10):
        for n in range(3):
            if n == 1 and t >= 2:
                continue       # node 1 stops heartbeating at t=2
            hm.report(Heartbeat(n, float(t), [DeviceStatus(0)]))
    assert failures == [1]
    assert hm.alive() == [0, 2]


def test_health_monitor_first_report_seeds_origin():
    """Regression: ``last_ok`` used to default to 0.0, so the first real
    wall-clock heartbeat (t >> 0) instantly declared every OTHER node
    stale-dead.  The first observation must seed a common origin."""
    hm = HealthMonitor(nodes=3, interval_s=1.0, dead_after=3)
    failures = []
    hm.on_failure = failures.append
    hm.report(Heartbeat(0, 1_000_000.0, [DeviceStatus(0)]))
    assert failures == [], "peers must not die on the first report"
    hm.report(Heartbeat(0, 1_000_002.9, [DeviceStatus(0)]))
    assert failures == []
    # now nodes 1/2 really are stale relative to the common origin
    hm.report(Heartbeat(0, 1_000_003.5, [DeviceStatus(0)]))
    assert sorted(failures) == [1, 2]
    assert hm.alive() == [0]


def test_cluster_failure_recovery():
    cfg = get_config("qwen3_moe_30b")
    hw = plan_lib.Hardware()
    cl = Cluster(cfg, hw, nodes=4, max_active=32, max_len=8192)
    wl = fixed_workload(64, 512, 256)
    cl.sched.submit(wl.prompts, wl.max_out)
    for _ in range(3):
        for node, eng in enumerate(cl.sched.engines):
            cl.sched._node_tick(node, eng)
    r = cl.fail_node(1)
    assert r["migrated"] + r["recomputed"] > 0
    # fail_node routes through the event-loop NODE_FAILURE handler — the
    # single §5.6 recovery path — so the monitor and report reflect it
    assert not cl.sched.health.failed.get(0) and cl.sched.health.failed[1]
    rep = cl.sched.run(max_ticks=50000)
    assert rep["completed"] == 64, "all sequences survive a node failure"
    assert rep["robustness"]["failed_nodes"] == [1]


def test_cluster_drain_node_graceful_handoff():
    """NODE_DRAIN (elastic scale-down) checkpoints + migrates every live
    sequence to a survivor — zero recompute, unlike NODE_FAILURE — and
    retires the node from rotation."""
    cfg = get_config("qwen3_moe_30b")
    cl = Cluster(cfg, plan_lib.Hardware(), nodes=2, max_active=32,
                 max_len=8192)
    wl = fixed_workload(24, 256, 2048)      # long enough to be mid-flight
    cl.sched.submit(wl.prompts, wl.max_out)
    for node, eng in enumerate(cl.sched.engines):
        cl.sched._node_tick(node, eng)
    r = cl.drain_node(1)
    assert r["drained"] and r["migrated"] > 0
    assert len(cl.sched.engines) == 1
    rep = cl.sched.run(max_ticks=50000)
    assert rep["completed"] == 24, "drain loses zero sequences"
    assert rep["robustness"]["drained_nodes"] == [1]
    assert not cl.sched.health.failed.get(1), \
        "a drained node is retired, not failed"
    # no survivor: the drain must refuse rather than strand the work
    cl2 = Cluster(cfg, plan_lib.Hardware(), nodes=1, max_active=32,
                  max_len=8192)
    cl2.sched.submit(wl.prompts[:4], [8] * 4)
    r2 = cl2.drain_node(0)
    assert not r2["drained"] and len(cl2.sched.engines) == 1


def test_cluster_elastic_scale_up():
    cfg = get_config("qwen3_moe_30b")
    cl = Cluster(cfg, plan_lib.Hardware(), nodes=2, max_active=32,
                 max_len=8192)
    wl = fixed_workload(48, 256, 128)
    cl.sched.submit(wl.prompts, wl.max_out)
    cl.add_node()
    rep = cl.sched.run(max_ticks=50000)
    assert rep["completed"] == 48
    assert len(cl.sched.engines) == 3


def test_checkpoint_restore_detects_corruption(tmp_path):
    cfg = reduced_config("qwen2_0_5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path / "c"), params)
    with open(str(tmp_path / "c" / "manifest.json")) as f:
        name, info = next(iter(json.load(f)["manifest"].items()))
    victim = str(tmp_path / "c" / info["file"])
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    with pytest.raises(ValueError, match=name.split("/")[0]):
        ckpt.restore(str(tmp_path / "c"))


# ---------------------------------------------------------------------------
# crash-resumable job ledger
# ---------------------------------------------------------------------------


def _ledger_master():
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8, seed=0)
    return BatchMaster([eng], SchedulerConfig(page_size=8))


def _ledger_reqs(rng, n=6):
    return [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, 100, 5)),
                         max_tokens=6) for i in range(n)]


def test_job_ledger_exactly_once(tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = JobLedger(p).open()
    led.record_submitted(["a", "b"])
    assert led.record_output("a", {"v": 1})
    assert not led.record_output("a", {"v": 2}), "duplicate must be refused"
    led.close()
    led2 = JobLedger(p).open()
    assert led2.finished == {"a": {"v": 1}}, "first write wins"
    assert led2.pending(["a", "b"]) == ["b"]
    led2.close()


def test_job_ledger_truncates_torn_trailing_line(tmp_path):
    p = str(tmp_path / "led.jsonl")
    led = JobLedger(p).open()
    led.record_output("a", {"v": 1})
    led.close()
    with open(p, "a") as f:        # SIGKILL mid-write: no trailing newline
        f.write('{"kind": "output", "custom_id": "b", "ro')
    led2 = JobLedger(p).open()
    assert led2.finished == {"a": {"v": 1}} and led2.torn_records == 1
    led2.record_output("c", {"v": 3})     # append lands on a clean line
    led2.close()
    led3 = JobLedger(p).open()
    assert set(led3.finished) == {"a", "c"}
    led3.close()


def test_job_ledger_resume_skips_finished(tmp_path, rng):
    """Kill-and-resume protocol, in process: a ledger holding the first 3
    committed rows of a 6-request batch resumes to the same bytes as the
    uninterrupted run, recomputing only the 3 unfinished requests."""
    reqs = _ledger_reqs(rng)
    full = run_resumable(_ledger_master(), reqs,
                         str(tmp_path / "full.jsonl"))
    assert full.resumed == 0 and full.computed == 6 and len(full.rows) == 6
    # craft the post-crash ledger: manifest + first 3 output records
    kept, dropped = 0, 0
    with open(str(tmp_path / "full.jsonl")) as f, \
            open(str(tmp_path / "crash.jsonl"), "w") as g:
        for line in f:
            if json.loads(line).get("kind") == "output":
                if kept >= 3:
                    dropped += 1
                    continue
                kept += 1
            g.write(line)
    assert kept == 3 and dropped == 3
    res = run_resumable(_ledger_master(), reqs,
                        str(tmp_path / "crash.jsonl"))
    assert res.resumed == 3 and res.computed == 3
    assert res.rows == full.rows, \
        "resumed output must equal the uninterrupted run"
    again = run_resumable(_ledger_master(), reqs,
                          str(tmp_path / "crash.jsonl"))
    assert again.resumed == 6 and again.computed == 0, \
        "a completed ledger is a no-op resume (zero recompute)"
    assert again.rows == full.rows


def test_job_ledger_rejects_duplicate_custom_ids(tmp_path, rng):
    reqs = _ledger_reqs(rng, 2)
    reqs[1].custom_id = reqs[0].custom_id
    with pytest.raises(LedgerError, match="duplicate custom_id"):
        run_resumable(_ledger_master(), reqs, str(tmp_path / "led.jsonl"))


# ---------------------------------------------------------------------------
# segmented ledger (chunked rotation, O(tail) resume)
# ---------------------------------------------------------------------------


def _seg_led(tmp_path, **kw):
    kw.setdefault("rotate_records", 4)
    kw.setdefault("fsync_every", 1)
    return SegmentedJobLedger(str(tmp_path / "led"), **kw)


def test_segmented_ledger_rotation_boundary_exact(tmp_path):
    led = _seg_led(tmp_path).open()
    for i in range(10):
        assert led.record_output(f"r{i}", {"v": i})
    # 10 rows at rotate_records=4 -> exactly 2 sealed segments + 2 live
    assert led.sealed_segments == 2 and led.live_segment == 2
    root = led.root
    led.close()
    for k, nrec in ((0, 4), (1, 4), (2, 2)):
        with open(os.path.join(root, f"seg-{k:08d}.jsonl")) as f:
            assert len(f.read().splitlines()) == nrec
    led2 = _seg_led(tmp_path).open()
    assert len(led2) == 10 and led2.sealed_segments == 2
    assert led2.replayed_segments == 1, "index resume parses only the tail"
    assert all(led2.read_row(f"r{i}") == {"v": i} for i in range(10)), \
        "locator reads must work for sealed AND live rows"
    led2.close()


def test_segmented_ledger_torn_line_newest_segment_only(tmp_path):
    led = _seg_led(tmp_path).open()
    for i in range(6):
        led.record_output(f"r{i}", {"v": i})    # seg0 sealed, seg1 live(2)
    led.close()
    sealed = os.path.join(led.root, "seg-00000000.jsonl")
    live = os.path.join(led.root, "seg-00000001.jsonl")
    # SIGKILL mid-write tears the LIVE tail; sealed files are never
    # re-read (index locators own them), so garbage there must survive
    # reopen untouched — proof the resume is O(tail), not O(job)
    with open(live, "a") as f:
        f.write('{"kind": "output", "custom_id": "r9", "ro')
    with open(sealed, "a") as f:
        f.write("SEALED-FILE-GARBAGE")
    led2 = _seg_led(tmp_path).open()
    assert led2.torn_records == 1 and len(led2) == 6
    assert open(sealed).read().endswith("SEALED-FILE-GARBAGE"), \
        "reopen must not touch sealed segments"
    assert not open(live, "rb").read().endswith(b"ro"), \
        "torn live tail must be truncated on disk"
    assert led2.read_row("r5") == {"v": 5}
    led2.close()


def test_segmented_ledger_sigkill_resume_across_boundary(tmp_path):
    """Real SIGKILL between rotations: every row sealed before the crash
    is durable (seals fsync) and a fresh process resumes with zero
    recompute of sealed rows, replaying only the tail segment."""
    import subprocess
    import sys as _sys
    prog = (
        "import os, signal, sys\n"
        "sys.path.insert(0, %r)\n"
        "from repro.runtime.ledger import SegmentedJobLedger\n"
        "led = SegmentedJobLedger(sys.argv[1], rotate_records=4,\n"
        "                         fsync_every=1000)\n"
        "led.open()\n"
        "for i in range(11):\n"
        "    led.record_output(f'r{i}', {'v': i})\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n"
        % os.path.join(os.path.dirname(__file__), "..", "src"))
    root = str(tmp_path / "led")
    p = subprocess.run([_sys.executable, "-c", prog, root],
                       capture_output=True)
    assert p.returncode == -9, p.stderr.decode()[-1000:]
    led = SegmentedJobLedger(root, rotate_records=4).open()
    # rows r0..r7 crossed two seal boundaries -> durable despite the huge
    # fsync_every (seals always fsync); r8..r10 were unsynced tail rows
    # and may or may not have landed — they are allowed to re-run
    assert led.sealed_segments == 2 and led.replayed_segments <= 1
    assert all(led.has(f"r{i}") for i in range(8)), \
        "sealed rows must never recompute"
    assert led.pending([f"r{i}" for i in range(8)]) == []
    led.close()


def test_segmented_ledger_duplicate_first_wins_across_segments(tmp_path):
    led = _seg_led(tmp_path).open()
    for i in range(5):
        led.record_output(f"r{i}", {"v": i})    # r0..r3 sealed, r4 live
    assert not led.record_output("r0", {"v": 999}), "in-memory refusal"
    assert led.duplicates_refused == 1
    led.close()
    # a crashed run's requeue race can append a duplicate to a LATER
    # segment; replay must keep the first committed row
    live = os.path.join(led.root, "seg-00000001.jsonl")
    with open(live, "a") as f:
        f.write(json.dumps({"kind": "output", "custom_id": "r0",
                            "row": {"v": 777}}) + "\n")
    led2 = _seg_led(tmp_path).open()
    assert led2.duplicates_refused == 1, "replay refuses the late copy"
    assert led2.read_row("r0") == {"v": 0}, "first write wins"
    assert len(led2) == 5
    led2.close()


def test_recovery_choice_crossover():
    cfg = get_config("llama3_2_1b")
    hw = plan_lib.Hardware()
    slow = recovery_choice(cfg, hw, kv_len=8192, prompt_len=8192,
                           inter_node_bw=0.05e9)
    fast = recovery_choice(cfg, hw, kv_len=8192, prompt_len=8192,
                           inter_node_bw=200e9)
    assert slow == "recompute"   # congested link: regenerate is faster
    assert fast == "migrate"     # fast link: move the KV snapshot


def test_plan_search_prefers_combine_for_moe():
    """The §5.4 search must pick B_moe >> B_attn-level batches for sparse
    models (the paper's core claim) and B_attn <= B_moe."""
    cfg = get_config("qwen3_moe_30b")
    hw = plan_lib.Hardware()
    plan = plan_lib.search_plan(cfg, hw, ctx=8192, new_tokens=1,
                                max_active=512)
    assert plan.b_moe == 512
    assert plan.b_attn <= plan.b_moe
    # per-token layer time must improve with combined batch size
    t_small = plan_lib.step_time(cfg, hw, plan, 8, 8192, 1) / 8
    t_big = plan_lib.step_time(cfg, hw, plan, 512, 8192, 1) / 512
    assert t_big < t_small / 4, "expert batching must amortize weight reads"


def test_dag_critical_path():
    d = plan_lib.DAG()
    d.add("a", 1.0)
    d.add("b", 2.0, ["a"])
    d.add("c", 0.5, ["a"])
    d.add("d", 1.0, ["b", "c"])
    t, path = d.critical_path()
    assert t == 4.0 and path == ["a", "b", "d"]


def test_coroutine_beats_static_on_longtail():
    """Headline reproduction: coroutine scheduling reduces BCT vs static
    binding on a long-tail workload (paper Table 5 direction)."""
    cfg = get_config("qwen3_moe_30b")
    hw = plan_lib.Hardware()
    wl = longtail_workload(256, mean_in=1024, mean_out=1024, sigma=1.2,
                           seed=3)
    cl = Cluster(cfg, hw, nodes=4, max_active=64, max_len=16384)
    rep = cl.run(wl)
    base = run_static_baseline(cfg, hw, wl, nodes=4)
    assert rep["completed"] == wl.n
    assert rep["bct_s"] < base["bct_s"], \
        f"coroutine {rep['bct_s']:.0f}s !< static {base['bct_s']:.0f}s"
