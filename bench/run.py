#!/usr/bin/env python3
"""Runs one benchmark cell on the chip(s) and prints its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration file, its mix (``bench/traffic/<mix>.json``),
its correctness limits (``bench/limits/<cell>.json``), its model family
(``bench/models/<model>.py``) and each of its metrics
(``bench/metrics/<metric>.py``) are found by name from there.

A run: builds the system under test with weights made on the chip from
the seed; warms up every shape by running one job of the mix from
another token stream; measures whole jobs for ``--seconds``; reads the
device's memory
peak; frees the program's state; compares a sample of the finished
requests with the float32 reference; and prints, as the last line of
stdout, ``{"correct", "attempted", "failed", "metrics", "device"[,
"breakdown"], "check"}``.  ``--trace 1`` traces part of the window and
reports the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
import time

T_START = time.perf_counter()     # set-up is timed from process start

import argparse                                                 # noqa: E402
import gc                                                       # noqa: E402
import importlib.util                                           # noqa: E402
import json                                                     # noqa: E402
import os                                                       # noqa: E402
import shutil                                                   # noqa: E402
import sys                                                      # noqa: E402
from dataclasses import dataclass                               # noqa: E402
from types import SimpleNamespace                               # noqa: E402
from typing import Optional                                     # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                                      # noqa: E402
import numpy as np                                              # noqa: E402

import check                                                    # noqa: E402
import drive                                                    # noqa: E402
import flops                                                    # noqa: E402
import trace_reduce                                             # noqa: E402
import workload as wl                                           # noqa: E402
from compile_clock import CompileClock                          # noqa: E402
from peaks import peaks_for                                     # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench_scratch", "trace")


class NoChip(RuntimeError):
    pass


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """Everything a run of one cell reads, found by name."""
    workload: dict
    config: dict            # the configuration file
    mix: dict
    limits: dict
    model: object           # bench/models/<model>.py
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        bench = _json(os.path.join(root, "BENCHMARK.json"))
        wls = {w["name"]: w for w in bench["workloads"]}
        if name not in wls:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = wls[name]
        entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        cfg = _json(os.path.join(root, entry["file"]))
        here = os.path.join(root, "bench")
        mine = lambda ms: [m for m in ms
                           if name in m.get("workloads", [name])]
        return cls(
            workload=w, config=cfg,
            mix=_json(os.path.join(here, "traffic", f"{w['traffic']}.json")),
            limits=_json(os.path.join(here, "limits", f"{name}.json")),
            model=_load(os.path.join(here, "models", f"{cfg['model']}.py"),
                        f"bench_model_{cfg['model']}"),
            end_to_end=mine(bench["end_to_end"]),
            per_layer=mine(bench["per_layer"]))


def require_chips(chips: int):
    """The first ``chips`` TPU devices; ``NoChip`` if there are fewer."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(path: str):
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program, however fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Tracer:
    """Starts and stops the profiler around the traced stretch, and
    snapshots the tallies and counters at both ends."""

    def __init__(self, on: bool, system, tally, log_dir: str):
        self.on, self.system, self.tally = on, system, tally
        self.log_dir = log_dir
        self.span = None
        self.marks = []
        self.t_start = 0.0

    def _mark(self):
        t = self.tally
        self.marks.append(dict(
            decode_tokens=t.decode_tokens, context_sum=t.context_sum,
            prefills=len(t.prefill_lens), counters=self.system.counters()))

    def start(self):
        if not self.on or self.span is not None or self.marks:
            return
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the harness's own spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._mark()
        self.t_start = time.perf_counter()
        self.span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self.span.__enter__()

    def stop(self):
        if self.span is None:
            return
        jax.block_until_ready([e.cache for e in self.system.engines])
        self.span.__exit__(None, None, None)
        self.span = None
        self._mark()
        jax.profiler.stop_trace()

    def stretch(self) -> Optional[SimpleNamespace]:
        """What the traced stretch produced (tally and counter deltas)."""
        if len(self.marks) != 2:
            return None
        a, b = self.marks
        return SimpleNamespace(
            decode_tokens=b["decode_tokens"] - a["decode_tokens"],
            context_sum=b["context_sum"] - a["context_sum"],
            prefill_lens=self.tally.prefill_lens[a["prefills"]:b["prefills"]],
            counters={k: b["counters"][k] - a["counters"][k]
                      for k in a["counters"]})


def another_job(elapsed: float, jobs: int, seconds: float) -> bool:
    """Whether the window starts another whole job: only while one more
    job, at the mean length of those run so far, ends by the deadline.
    The first job always runs, so a window is one or more whole jobs."""
    return jobs == 0 or elapsed * (jobs + 1) / jobs <= seconds


def _traffic(cell: Cell, system, seed: int, seconds: float, tally,
             tracer: Tracer, stream: int):
    """Runs the mix: the warm-up (``stream`` 0: one whole job from its own
    token stream, which meets every shape of the window, down to the
    batches that prefill together, since the order of lengths is the
    same) or the measured window (``stream`` 1: whole jobs back to back
    for ``seconds``, as ``another_job`` says).  A traced
    window traces the stretch the mix's ``trace`` entry names: from the
    first scheduler record ``skip_s`` into the window to the first one
    ``seconds`` after that.  Returns the window's wall seconds."""
    mix, vocab = cell.mix, cell.config["vocab_size"]
    specs = wl.specs(mix, cell.config["engine"]["max_len"])
    n = len(specs)
    t0 = time.perf_counter()
    span = mix.get("trace", {})

    def tick():
        now = time.perf_counter()
        if now - t0 >= span["skip_s"]:
            tracer.start()
        # records come in bursts, one a page: timing the stretch from its
        # own start keeps a burst from opening and closing it at once
        if tracer.span is not None and now - tracer.t_start >= span["seconds"]:
            tracer.stop()

    run_one = {"job": drive.run_job, "rollout": drive.run_rollout}[
        mix["mode"]]
    while another_job(time.perf_counter() - t0, tally.jobs, seconds):
        reqs = wl.requests(mix, specs, vocab, seed, stream, tally.jobs * n)
        with jax.profiler.TraceAnnotation("bench.job"):
            run_one(system, reqs, tally, tick if tracer.on else None)
        if stream == 0:
            break
    tracer.stop()
    return time.perf_counter() - t0


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        peaks, t_start: float, log=print) -> dict:
    """One run of ``cell`` on ``devices`` (whose peaks are ``peaks``);
    returns the result dict."""
    clock = CompileClock()
    system = drive.System(cell.config, cell.model, devices, seed)
    warm = drive.Tally()
    _traffic(cell, system, seed, seconds, warm, Tracer(False, None, None, ""),
             0)
    jax.block_until_ready([e.cache for e in system.engines])
    setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s={setup_s:.3f} warmup_tokens={warm.tokens} "
        f"compile={clock.snapshot()}")

    if trace:
        system.trace_spans()
    tally = drive.Tally()
    tracer = Tracer(trace, system, tally, TRACE_DIR)
    before_c, before_k = system.counters(), clock.snapshot()
    window_s = _traffic(cell, system, seed, seconds, tally, tracer, 1)
    after_c, in_window = system.counters(), CompileClock.since(
        before_k, clock.snapshot())
    log(f"[window] window_s={window_s:.3f} jobs={tally.jobs} "
        f"tokens={tally.tokens} attempted={tally.attempted} "
        f"failed={tally.failed} in_window_compile={in_window}")

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    counters = {k: after_c[k] - before_c[k] for k in after_c}
    slots = system.slots
    system.close()
    del system
    gc.collect()

    limits = cell.limits
    ref = cell.model.Reference(cell.config, seed, devices[0],
                               cell.config["engine"]["max_len"])
    rng = np.random.default_rng([seed & (2 ** 64 - 1), 2])
    picked = check.sample(tally.done, rng, limits["sample_requests"])
    t_ref = time.perf_counter()
    numbers = check.readings(ref, picked)
    ok, lines = check.verdict(numbers, limits["limits"])
    correct = ok and tally.failed == 0 and tally.attempted > 0
    log(f"[check] requests={len(picked)} tokens="
        f"{sum(len(d.tokens) for d in picked)} reference_s="
        f"{time.perf_counter() - t_ref:.3f}")

    summary = stretch = None
    if trace:
        summary = trace_reduce.reduce(trace_reduce.find_xplane(TRACE_DIR))
        stretch = tracer.stretch()
    ctx = SimpleNamespace(
        config=cell.config, mix=cell.mix, tally=tally, counters=counters,
        window_s=window_s, setup_s=setup_s, chips=len(devices), slots=slots,
        dims=flops.dims(cell.config), peaks=peaks,
        memory_peak_bytes=peak, trace=summary, stretch=stretch)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = _load(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                       f"bench_metric_{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": tally.attempted,
              "failed": tally.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["check"] = {ln["name"]: {"value": ln["value"],
                                    "limit": ln["limit"]} for ln in lines}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    try:
        devices = require_chips(int(cell.workload["chips"]))
        peaks = peaks_for(devices[0].device_kind)
    except (NoChip, KeyError) as e:
        print(f"[bench] {e}; no result", file=sys.stderr)
        return 2
    use_compile_cache(CACHE_DIR)
    log = lambda s: print(s, file=sys.stderr, flush=True)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 peaks, T_START, log)
    for name, v in result["check"].items():
        log(f"check {name} value={v['value']} limit={v['limit']}")
    log(f"check correct={result['correct']} failed={result['failed']} "
        f"attempted={result['attempted']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
