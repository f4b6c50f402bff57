"""The trace reduction, on a trace recorded on one TPU v5e: two decode
pages of qwen2_0_5b at its published widths (``data/decode_trace``).

Each number of ``trace_reduce.reduce`` is checked against the same
quantity worked out here another way: busy time by an endpoint sweep
instead of an interval merge, program time summed straight off the
module line, and the longest idle gap found by the sweep and named by a
host span that really covers it.
"""
import glob
import gzip
import os
import shutil

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))


@pytest.fixture(scope="module", params=TRACES,
                ids=[os.path.basename(p) for p in TRACES])
def recorded(request, tmp_path_factory):
    from jax.profiler import ProfileData
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with gzip.open(request.param, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    pd = ProfileData.from_file(path)
    host, ops, modules = [], [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if plane.name.startswith("/host:"):
                    host.append(iv)
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(iv)
                elif (plane.name == "/device:TPU:0"
                      and line.name == "XLA Modules"):
                    modules.append(iv)
    return path, tr.reduce(path), host, ops, modules


def _sweep(ops, lo, hi):
    """Busy time and the longest idle gap in [lo, hi], by counting open
    intervals at each endpoint."""
    pts = sorted([(max(a, lo), 1) for a, b, _ in ops if b > lo and a < hi]
                 + [(min(b, hi), -1) for a, b, _ in ops if b > lo and a < hi])
    busy, depth, last, idle_from, longest = 0, 0, lo, lo, (0, lo, lo)
    for t, d in pts:
        if depth > 0:
            busy += t - last
        elif t - idle_from > longest[0]:
            longest = (t - idle_from, idle_from, t)
        depth += d
        last = t
        if depth == 0:
            idle_from = t
    if hi - idle_from > longest[0] and depth == 0:
        longest = (hi - idle_from, idle_from, hi)
    return busy, longest


def test_window_and_busy(recorded):
    path, s, host, ops, _ = recorded
    (lo, hi, _), = [h for h in host if h[2] == tr.WINDOW_SPAN]
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    busy, _ = _sweep(ops, lo, hi)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < s.busy_s < s.window_s


def test_program_time(recorded):
    path, s, _, _, modules = recorded
    total = {}
    for a, b, name in modules:
        key = tr._MODULE_ID.sub("", name)
        total[key] = total.get(key, 0) + (b - a)
    assert {k: v[0] for k, v in s.programs.items()} == \
        pytest.approx({k: v / 1e9 for k, v in total.items()})
    mega, n = s.program_seconds(r"_mega")
    assert n >= 2 and mega > 0          # two decode pages, at least
    assert mega < s.busy_s + 1e-9


def test_longest_gap_is_named_by_a_covering_span(recorded):
    path, s, host, ops, _ = recorded
    (lo, hi, _), = [h for h in host if h[2] == tr.WINDOW_SPAN]
    _, (length, a, b) = _sweep(ops, lo, hi)
    name, secs = s.gaps[0]
    assert secs == pytest.approx(length / 1e9)
    assert [g for _, g in s.gaps] == sorted((g for _, g in s.gaps),
                                            reverse=True)
    covering = [h for h in host if h[2] == name
                and min(b, h[1]) - max(a, h[0]) > 0]
    assert covering, (name, a, b)


def test_breakdown_shape(recorded):
    _, s, _, _, _ = recorded
    bd = tr.breakdown(s)
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [v for _, v in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_self_time_of_nested_ops():
    """A loop's self time excludes its body; kinds drop the ``%`` and
    the numeric suffix."""
    from collections import defaultdict
    ops = defaultdict(lambda: [0.0, 0])
    tr._self_times([(0, 100, "%while.3 = (s32[]) while(...)"),
                    (10, 30, "%fusion.7 = f32[8] fusion(...)"),
                    (40, 90, "%fused_sample.1 = (f32[8]) custom-call(...)"),
                    (50, 60, "%copy.2 = f32[8] copy(...)"),
                    (120, 130, "%fusion.9 = f32[8] fusion(...)")], ops)
    assert dict(ops) == {"while": [30, 1], "fusion": [30, 2],
                         "fused_sample": [40, 1], "copy": [10, 1]}
    assert tr.op_kind("%constant_dynamic-update-slice_fusion.5 = bf16[2]"
                      " fusion(...)") == "constant_dynamic-update-slice_fusion"
