"""Streaming job driver at scale: bounded-window feed, elastic replicas.

Full mode drives a 100k-request long-tail jsonl job through
``StreamingJobDriver`` on SimEngine replicas twice — a static 1-replica
run and an identical run that ``scale_up()``s a second replica mid-job —
and reports sustained req/s for both (the elastic run must be faster)
plus the peak resident window (must stay under the configured bound).

``--smoke`` is the CI variant: 10k requests with one mid-job
``scale_up()`` AND one ``drain()``, then a subprocess SIGKILL mid-job
followed by a resume whose merged output must be byte-identical to the
uninterrupted run's (only the ledger's tail segment may be replayed).
The children run SimEngine replicas and touch no device: keep it so, since
a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import emit, write_json
from repro.configs import get_config
from repro.core import plan as plan_lib
from repro.core.scheduler import SchedulerConfig
from repro.data.pipeline import LongTailRequestStream
from repro.driver import DriverConfig, StreamingJobDriver
from repro.runtime.cluster import sim_node_group

CFG_NAME = "qwen3_moe_30b"
MAX_ACTIVE = 256
WINDOW = 4096


def _factory(cfg, hw, plan):
    def factory(rid):
        return sim_node_group(cfg, hw, nodes=2, first_node_id=rid * 100,
                              max_active=MAX_ACTIVE, max_len=8192,
                              page_size=64, plan=plan)
    return factory


def _driver(inp, out, ledger, factory, rotate_records=50_000):
    return StreamingJobDriver(
        inp, out, ledger, factory,
        cfg=DriverConfig(window=WINDOW, replicas=1,
                         rotate_records=rotate_records),
        sched_cfg=SchedulerConfig(page_size=64))


def _rates(timeline, split_t):
    """Sustained req/s before/after a driver-timeline instant."""
    pre = [p for p in timeline if p["t"] <= split_t]
    post = [p for p in timeline if p["t"] > split_t]
    def rate(pts):
        if len(pts) < 2:
            return 0.0
        dt = pts[-1]["t"] - pts[0]["t"]
        return (pts[-1]["completed"] - pts[0]["completed"]) / max(dt, 1e-9)
    return rate(pre), rate(post)


def run_job(n, root, *, scale_at_frac=None, drain_at_frac=None,
            rotate_records=50_000, seed=7):
    """One full driver job over a fresh n-request input; returns
    (DriverResult, scale_time, wall_s, output_path)."""
    cfg = get_config(CFG_NAME)
    hw = plan_lib.Hardware()
    plan = plan_lib.search_plan(cfg, hw, ctx=4096, new_tokens=1,
                                max_active=MAX_ACTIVE)
    inp = os.path.join(root, "in.jsonl")
    if not os.path.exists(inp):
        LongTailRequestStream(n, seed=seed, mean_in=48,
                              mean_out=24).write_jsonl(inp)
    out = os.path.join(root, "out.jsonl")
    drv = _driver(inp, out, os.path.join(root, "ledger"),
                  _factory(cfg, hw, plan), rotate_records=rotate_records)
    marks = {"scale_t": None}

    def hook(d, rnd):
        if scale_at_frac is not None and marks["scale_t"] is None \
                and d.completed >= n * scale_at_frac:
            d.scale_up()
            marks["scale_t"] = d.sim_now()
        if drain_at_frac is not None and not marks.get("drained") \
                and d.completed >= n * drain_at_frac \
                and len(d._open_replicas()) > 1:
            d.drain(d.replicas[0].rid, requeue=True)
            marks["drained"] = True

    t0 = time.perf_counter()
    res = drv.run(on_round=hook)
    wall = time.perf_counter() - t0
    return res, drv.timeline, marks["scale_t"], wall, out


def _full(n):
    payload = {"n": n, "window": WINDOW, "max_active": MAX_ACTIVE}
    with tempfile.TemporaryDirectory() as r1:
        base, _, _, wall1, _ = run_job(n, r1)
        assert base.status == "completed" and base.merged_records == n
        assert base.peak_resident <= WINDOW
        base_rps = n / base.makespan_s
        emit("driver.static_1_replica", base.makespan_s * 1e6,
             f"rps={base_rps:.0f} peak_resident={base.peak_resident} "
             f"wall={wall1:.0f}s")
        payload["static"] = {
            "makespan_s": base.makespan_s, "sustained_rps": base_rps,
            "peak_resident": base.peak_resident, "wall_s": wall1,
            "sealed_segments": base.report["ledger"]["sealed_segments"]}
    with tempfile.TemporaryDirectory() as r2:
        el, timeline, scale_t, wall2, _ = run_job(n, r2, scale_at_frac=0.3)
        assert el.status == "completed" and el.merged_records == n
        assert el.scale_ups == 1 and el.peak_resident <= WINDOW
        el_rps = n / el.makespan_s
        pre, post = _rates(timeline, scale_t)
        emit("driver.scale_up_mid_job", el.makespan_s * 1e6,
             f"rps={el_rps:.0f} pre={pre:.0f} post={post:.0f} "
             f"speedup={base_rps and el_rps / base_rps:.2f}x")
        payload["elastic"] = {
            "makespan_s": el.makespan_s, "sustained_rps": el_rps,
            "scale_up_at_s": scale_t, "pre_scale_rps": pre,
            "post_scale_rps": post, "peak_resident": el.peak_resident,
            "wall_s": wall2}
        payload["speedup"] = el_rps / base_rps
        assert el_rps > base_rps, "scale_up must raise sustained req/s"
        assert post > pre, "post-scale-up rate must exceed pre"
    write_json("streaming_driver", payload)


def _worker(argv):
    """Subprocess body for the SIGKILL leg: run the job, kill -9 self
    after ``kill_after`` rows are journaled."""
    inp, out, ledger, kill_after = argv[0], argv[1], argv[2], int(argv[3])
    cfg = get_config(CFG_NAME)
    hw = plan_lib.Hardware()
    plan = plan_lib.search_plan(cfg, hw, ctx=4096, new_tokens=1,
                                max_active=MAX_ACTIVE)
    drv = _driver(inp, out, ledger, _factory(cfg, hw, plan),
                  rotate_records=1000)

    def hook(d, rnd):
        if kill_after >= 0 and d.completed >= kill_after:
            os.kill(os.getpid(), signal.SIGKILL)

    res = drv.run(on_round=hook)
    print(json.dumps({"status": res.status, "completed": res.completed,
                      "skipped": res.skipped_resume,
                      "replayed": res.report["ledger"]["replayed_segments"],
                      "merged": res.merged_records}))


def _smoke(n=10_000):
    payload = {"n": n, "window": WINDOW, "mode": "smoke"}
    # leg 1: elasticity — one scale_up AND one requeue-drain mid-job
    with tempfile.TemporaryDirectory() as root:
        res, _, _, wall, out = run_job(n, root, scale_at_frac=0.2,
                                       drain_at_frac=0.5,
                                       rotate_records=1000)
        assert res.status == "completed" and res.merged_records == n
        assert res.scale_ups == 1 and res.peak_resident <= WINDOW
        emit("driver.smoke_elastic", res.makespan_s * 1e6,
             f"rps={n / res.makespan_s:.0f} requeued={res.requeued} "
             f"wall={wall:.0f}s")
        payload["elastic"] = {
            "makespan_s": res.makespan_s, "requeued": res.requeued,
            "peak_resident": res.peak_resident, "wall_s": wall}
        clean = open(out, "rb").read()
    # leg 2: SIGKILL mid-job + resume == byte-identical merged output
    with tempfile.TemporaryDirectory() as root:
        inp = os.path.join(root, "in.jsonl")
        LongTailRequestStream(n, seed=7, mean_in=48,
                              mean_out=24).write_jsonl(inp)
        out = os.path.join(root, "out.jsonl")
        led = os.path.join(root, "ledger")
        args = [sys.executable, os.path.abspath(__file__), "--worker",
                inp, out, led]
        p = subprocess.run(args + [str(n // 3)], capture_output=True)
        assert p.returncode == -signal.SIGKILL, p.stderr.decode()[-2000:]
        p = subprocess.run(args + ["-1"], capture_output=True)
        assert p.returncode == 0, p.stderr.decode()[-2000:]
        info = json.loads(p.stdout.decode().strip().splitlines()[-1])
        assert info["status"] == "completed" and info["merged"] == n
        assert info["skipped"] > 0, "resume must skip journaled rows"
        assert info["replayed"] <= 1, "resume must replay only the tail"
        resumed = open(out, "rb").read()
        assert resumed == clean, "kill+resume output != clean output"
        emit("driver.smoke_kill_resume", 0.0,
             f"skipped={info['skipped']} replayed={info['replayed']} "
             f"bytes={len(clean)}")
        payload["kill_resume"] = {"skipped": info["skipped"],
                                  "replayed_segments": info["replayed"],
                                  "merged_bytes": len(clean),
                                  "byte_identical": True}
    write_json("streaming_driver_smoke", payload)


def run():
    _full(100_000)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("-n", type=int, default=None)
    ap.add_argument("--worker", nargs=4, metavar="ARG")
    a = ap.parse_args()
    if a.worker:
        _worker(a.worker)
    elif a.smoke:
        _smoke(a.n or 10_000)
    else:
        _full(a.n or 100_000)
