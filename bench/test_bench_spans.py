"""Idle time put down to host spans (``idle_spans``), and the three
per-layer readers built on it: on the recorded v5e trace, on a synthetic
nest of spans, on hand-built contexts, and on a traced run of a cell at
a CPU test's size."""
import glob
import gzip
import os
import shutil
import time
from types import SimpleNamespace

import pytest

import idle_spans as isp
import trace_reduce as tr
from conftest import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))
READERS = ("install_share", "jit_build_share", "sched_idle_share")
PROGRAM = ("engine.node.", "engine.sched.", "engine.prim.")


def _reader(name):
    import run as R
    return R._load(os.path.join(HERE, "metrics", f"{name}.py"),
                   f"bench_metric_{name}")


@pytest.fixture(scope="module", params=TRACES,
                ids=[os.path.basename(p) for p in TRACES])
def recorded(request, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with gzip.open(request.param, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def _idle_by_sweep(path):
    """Window less busy, by counting open device intervals at each
    endpoint (no interval merge)."""
    from jax.profiler import ProfileData
    host, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith("/host:") and \
                        ev.name == tr.WINDOW_SPAN:
                    host.append(iv)
                elif plane.name.startswith("/device:TPU") and \
                        line.name == "XLA Ops":
                    ops.append(iv)
    (lo, hi), = host
    pts = sorted([(max(a, lo), 1) for a, b in ops if b > lo and a < hi]
                 + [(min(b, hi), -1) for a, b in ops if b > lo and a < hi])
    busy, depth, last = 0, 0, lo
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return (hi - lo - busy) / 1e9


def test_idle_by_span_sums_to_window_less_busy(recorded):
    s = isp.summarize(recorded)
    t = tr.reduce(recorded)
    idle = _idle_by_sweep(recorded)
    assert sum(s.idle_by_span.values()) == pytest.approx(idle, rel=1e-9)
    assert s.idle_s == pytest.approx(idle, rel=1e-9)
    assert s.window_s == pytest.approx(t.window_s)
    assert s.idle_s == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert all(v > 0 for v in s.idle_by_span.values())


def test_innermost_span_takes_the_idle_time(recorded):
    """The recorded pages' install flush ran with the device idle: the
    idle time goes to the flush, which the longest-gap naming gives to
    the ``decode_page`` around it."""
    s = isp.summarize(recorded)
    flush = s.idle_by_span["engine.flush_pending_installs"]
    assert flush == pytest.approx(s.span_s["engine.flush_pending_installs"],
                                  rel=1e-6)
    assert flush > 0.1
    assert tr.reduce(recorded).gaps[0][0] == "engine.decode_page"


def test_nested_spans_and_gaps():
    """Each idle instant goes to the innermost covering span, the one
    opened last; uncovered instants to ``host (no span)``."""
    spans = [(0, 100, "engine.sched.round"),
             (10, 60, "engine.sched.module_ready"),
             (20, 40, "engine.node.install"),
             (45, 60, "engine.node.block_wait"),
             (70, 90, "engine.sched.refill")]
    idle = [(0, 5), (15, 30), (35, 50), (80, 95), (100, 120)]
    got = isp.attribute(idle, spans)
    assert got == pytest.approx({
        "engine.sched.round": 10e-9,            # 0-5, 90-95
        "engine.sched.module_ready": 10e-9,     # 15-20, 40-45
        "engine.node.install": 15e-9,           # 20-30, 35-40
        "engine.node.block_wait": 5e-9,         # 45-50
        "engine.sched.refill": 10e-9,           # 80-90
        isp.NO_SPAN: 20e-9})                    # 100-120
    assert sum(got.values()) == pytest.approx(
        sum(b - a for a, b in idle) / 1e9)


def _ctx(idle_by_span, span_s, window_s=2.0):
    s = isp.SpanSummary(window_s=window_s,
                        idle_s=sum(idle_by_span.values()),
                        idle_by_span=idle_by_span, span_s=span_s)
    return SimpleNamespace(spans=s, trace=object())


def test_readers_on_hand_built_context():
    ctx = _ctx({"engine.sched.round": 0.1, "engine.prim.combine": 0.2,
                "engine.node.install": 0.4, "engine.decode_page": 0.05,
                isp.NO_SPAN: 0.05},
               {"engine.sched.round": 2.0, "engine.node.install": 0.5,
                "engine.node.compile": 0.25, "engine.prim.combine": 0.3})
    assert _reader("install_share").read(ctx) == pytest.approx(25.0)
    assert _reader("jit_build_share").read(ctx) == pytest.approx(12.5)
    assert _reader("sched_idle_share").read(ctx) == pytest.approx(15.0)
    quiet = _ctx({"engine.node.megastep": 0.3},
                 {"engine.sched.round": 2.0, "engine.node.megastep": 1.0})
    assert _reader("jit_build_share").read(quiet) == 0.0
    assert _reader("install_share").read(quiet) == 0.0
    assert _reader("sched_idle_share").read(quiet) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_program_spans(name):
    """A program whose only spans are the harness's reads nothing, and an
    untraced run reads nothing."""
    harness = _ctx({"engine.decode_page": 0.8, "bench.pump": 0.1},
                   {"engine.decode_page": 1.5, "bench.pump": 0.5})
    assert _reader(name).read(harness) is None
    untraced = SimpleNamespace(spans=None, trace=None)
    assert _reader(name).read(untraced) is None


@pytest.fixture(scope="module")
def traced_run(cpu, cpu_peaks):
    import run as R
    cell = tiny(R.Cell.load(R.ROOT, "qwen2_0_5b.rollout_job"))
    res = R.run(cell, 2 ** 33 + 29, 0.5, True, cpu, cpu_peaks,
                time.perf_counter(), log=lambda s: None)
    return res, isp.summarize(tr.find_xplane(R.TRACE_DIR))


def test_traced_run_reports_the_span_metrics(traced_run):
    res, _ = traced_run
    assert res["failed"] == 0 and res["attempted"] > 0
    for name in READERS:
        assert name in res["metrics"], name
        assert 0.0 <= res["metrics"][name]["value"] <= 100.0


def test_traced_run_idle_falls_under_program_spans(traced_run):
    """On the CPU no device plane exists, so the whole stretch is idle;
    the program's own spans cover nearly all of it."""
    _, s = traced_run
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s)
    assert s.idle_under(*PROGRAM) >= 0.8 * s.idle_s


def test_readers_look_where_the_harness_traces():
    import run as R
    assert os.path.normpath(isp.TRACE_DIR) == os.path.normpath(R.TRACE_DIR)
