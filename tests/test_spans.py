"""The program's profiler spans and host-cost counters, on a 2-layer
engine job traced on the CPU.

One traced job on two engines meets every span: fork fan-out, sampled
and log-prob requests, a device pool small enough that the governor
preempts and restores, a rebalancing MIGRATE, PARTITION at the tail,
and a second submit of a prompt the prefix index already holds.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.core.events import PrimitiveEvent
from repro.core.scheduler import CoroutineScheduler, SchedulerConfig
from repro.runtime.engine import NodeEngine
from repro.sampling import SAMPLE_TIERS, SamplingParams

SPANS = (
    "engine.sched.round", "engine.sched.refill", "engine.sched.module_ready",
    "engine.sched.sync", "engine.sched.sync_drain", "engine.sched.seq_done",
    "engine.sched.seq_preempt", "engine.sched.page_boundary",
    "engine.sched.long_tail", "engine.sched.migrate",
    "engine.prim.yield", "engine.prim.combine", "engine.prim.partition",
    "engine.prim.migrate", "engine.prim.fork",
    "engine.node.install", "engine.node.sampling_state",
    "engine.node.megastep", "engine.node.block_wait",
    "engine.node.apply_block", "engine.node.prefill_forward",
    "engine.node.prefix_graft", "engine.node.first_token",
    "engine.node.gather", "engine.node.materialize", "engine.node.restore",
    "engine.node.compile")
COUNTERS = ("install_s", "slots_installed", "jit_builds", "jit_build_s")


def _host_spans(log_dir):
    """(start, end, name, metadata, line) of every ``engine.*`` host
    event in the profile under ``log_dir``."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats), li))
    return out


def _sampling(g):
    return (SamplingParams() if g % 2 == 0 else
            SamplingParams(temperature=0.8, top_k=20, seed=40 + g))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = reduced_config("llama3_2_1b")
    engines = [NodeEngine(cfg, node_id=i, max_active=3, max_len=64,
                          page_size=8, seed=0, device_pages=8)
               for i in range(2)]
    sched = CoroutineScheduler(
        engines, SchedulerConfig(page_size=8, longtail_min_remaining=8))
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(2, 100, 17)) for _ in range(5)]
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        sched.submit(prompts, [24] * 5,
                     sampling=[_sampling(g) for g in range(5)],
                     logprobs=[g % 2 == 1 for g in range(5)], n=2)
        first = sched.run(max_ticks=4000)
        sched.submit(prompts[:1], [12])         # a prefix-index hit
        second = sched.run(max_ticks=4000)
    assert first["status"] == second["status"] == "completed"
    return sched, engines, _host_spans(log_dir), second


@pytest.mark.parametrize("name", SPANS)
def test_every_span_appears(traced, name):
    _, _, spans, _ = traced
    assert any(s[2] == name for s in spans), name


def test_node_spans_nest_in_scheduler_spans(traced):
    """Every engine call the scheduler makes runs inside the span of the
    handler (or round) that made it, on the same thread."""
    _, _, spans, _ = traced
    sched = [s for s in spans if s[2].startswith("engine.sched.")]
    node = [s for s in spans if s[2].startswith("engine.node.")]
    assert node
    for a, b, name, _, line in node:
        assert any(sa <= a and b <= sb and sl == line
                   for sa, sb, _, _, sl in sched), name


def test_spans_carry_their_keys(traced):
    """Per-sequence identity and executable keys ride span metadata."""
    _, _, spans, _ = traced
    installs = [s for s in spans if s[2] == "engine.node.install"]
    assert installs and all("seqs" in s[3] for s in installs)
    builds = [s for s in spans if s[2] == "engine.node.compile"]
    assert builds and all("key" in s[3] for s in builds)
    rounds = [s for s in spans if s[2] == "engine.sched.round"]
    assert all("tick" in s[3] for s in rounds)
    pages = [s for s in spans if s[2] == "engine.node.megastep"]
    assert pages and all("steps" in s[3] for s in pages)
    assert {s[3].get("tier") for s in pages} <= {"greedy", *SAMPLE_TIERS}


def test_report_sums_engine_counters(traced):
    sched, engines, spans, report = traced
    eng = report["engine"]
    assert set(eng) == set(COUNTERS) | {"sample_tier_pages"}
    for k in COUNTERS:
        assert eng[k] == pytest.approx(sum(getattr(e, k) for e in engines))
    assert eng["sample_tier_pages"] == {
        t: sum(e.sample_tier_pages[t] for e in engines)
        for t in SAMPLE_TIERS}
    sampled = [s for s in spans if s[2] == "engine.node.megastep"
               and s[3]["tier"] != "greedy"]
    assert sampled and sum(eng["sample_tier_pages"].values()) == len(sampled)
    builds = [s for s in spans if s[2] == "engine.node.compile"]
    assert eng["jit_builds"] == len(builds)
    assert eng["install_s"] > 0 and eng["jit_build_s"] > 0


def _job(eng, prompts, max_out):
    """One greedy job on a fresh scheduler; returns the sequences that
    COMBINE admitted."""
    sched = CoroutineScheduler([eng], SchedulerConfig(page_size=8))
    sched.submit(prompts, max_out)
    admitted = [r for r in sched.stream(max_ticks=500)
                if isinstance(r, PrimitiveEvent) and r.primitive == "combine"]
    assert sched.report()["status"] == "completed"
    return len(admitted)


def test_install_and_build_counters_on_a_repeated_job(rng):
    """``slots_installed`` counts the slots COMBINE admitted (not the
    pow2 padding); a job's first run builds executables, and the same
    job again builds none."""
    cfg = reduced_config("llama3_2_1b")
    eng = NodeEngine(cfg, max_active=3, max_len=64, page_size=8, seed=0,
                     enable_prefix=False)
    prompts = [list(rng.integers(2, 100, n)) for n in (5, 9, 12, 7, 15)]
    max_out = [6, 11, 9, 14, 4]
    admitted = _job(eng, prompts, max_out)
    assert admitted == len(prompts)
    assert eng.slots_installed == admitted
    assert eng.install_s > 0
    assert eng.jit_builds > 0 and eng.jit_build_s > 0
    builds, installed = eng.jit_builds, eng.slots_installed
    assert _job(eng, prompts, max_out) == len(prompts)
    assert eng.jit_builds == builds
    assert eng.slots_installed - installed == len(prompts)
