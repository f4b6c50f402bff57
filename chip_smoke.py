#!/usr/bin/env python3
"""Chip smoke test: the serving path once, end to end, on a TPU.

Serves qwen2_0_5b at its published widths (24 layers, d_model 896,
14/2 heads, d_ff 4864, vocab 151936, bf16; random weights from
``--seed``) through the construction every server uses
(``repro.launch.serve.build_master``): BatchMaster ->
CoroutineScheduler -> NodeEngine -> fused prefill/decode megasteps ->
host KV store.  One process drives the chip(s) and starts no child.
Every failed check raises, so the exit code is nonzero; on success the
last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

    python chip_smoke.py                # one chip: serve, check, N = 1
    python chip_smoke.py --four-chips   # four engines, one per chip, N = 4
    JAX_PLATFORMS=cpu PYTHONPATH=src python chip_smoke.py --reduced

Without a TPU it exits nonzero before any work.  ``--reduced`` (the
tiny same-family config of ``reduced_config``) is the only way to run
it elsewhere — the CPU rehearsal — and never prints the success line.
The compile cache is ``repro.launch.compile_cache``'s: a second run in
the same checkout reports the warm compile time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

ARCH = "qwen2_0_5b"
# Engine sizes, read off memory_analysis() of the programs this script
# runs, compiled for one v5e (16 GiB HBM): the megastep at 32 slots x
# 2048 positions takes 1.9 GiB of arguments (1.2 GiB weights, 0.75 GiB
# KV cache) and 4.5 GiB of temporaries; at 64 x 2048 it is 2.7 + 9.0 GiB,
# too close to the chip's 16 GiB beside the f32 reference's 2.3 GiB.
MAX_ACTIVE = 32
MAX_LEN = 2048
PAGE = 32
# (prompt length, max_tokens, greedy, top_logprobs or None).  Output
# lengths are 1 + whole pages (prefill emits the first token), so every
# decode page is one 32-step megastep; the two longest requests (one
# greedy, one sampled, both with logprobs) keep the batch's sampling
# plan unchanged to the end — one megastep program in all.
REQUESTS = [(256, 97, True, 5), (256, 97, True, 5), (512, 65, True, None),
            (64, 33, True, None), (96, 97, False, 5), (480, 65, False, None),
            (400, 33, False, None), (160, 97, False, None)]
CHECKED = (0, 1)        # greedy requests whose cached logits are checked
# Cached-decode log-probs vs the float32 full forward: the cached path
# holds weights, activations, the KV cache and the logits in bf16
# (8-bit significand, 2^-9 relative rounding), and the roundings of 24
# layers compound to a few percent of the logit scale.  10% of the
# reference logits' standard deviation is well above that and well below
# what the logits at the neighbouring position differ by — a cache or
# logprob plane read one position off fails it (checked below).
TOL_STD = 0.10
# four-chip phase: 16 greedy requests over four engines with two slots
# and a 6-page device pool each (a memory-limited deployment: the
# governor preempts to host KV when a node's pages cross the high
# watermark); node 0 (requests 0, 4, 8, 12) gets the long outputs, so its
# preempted sequences queue while the others drain, and MIGRATE moves
# their host KV to the idle chips
FOUR_ACTIVE = 2
FOUR_PAGES = 6
FOUR_REQUESTS = [(128, 97 if i % 4 == 0 else 33) for i in range(16)]


class CompileClock:
    """Seconds JAX spent building executables (backend compile, or load
    from the persistent cache), counted through jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _prompts(rng, lengths, vocab):
    return [[int(t) for t in rng.integers(2, vocab, n)] for n in lengths]


def _serve(master, reqs):
    t0 = time.perf_counter()
    bo = master.run(master.submit(reqs))
    wall = time.perf_counter() - t0
    if bo.status != "completed" or bo.request_counts["failed"] != 0 \
            or bo.request_counts["completed"] != len(reqs):
        raise RuntimeError(f"batch {bo.status}: {bo.request_counts}")
    rows = {r["custom_id"]: r for r in bo.results}
    for req in reqs:
        toks = rows[req.custom_id]["response"]["tokens"]
        if len(toks) != req.max_tokens:
            raise RuntimeError(f"{req.custom_id}: {len(toks)} tokens, "
                               f"max_tokens {req.max_tokens}, no stop set")
    return rows, wall


def _log_softmax(x):
    x = np.asarray(x, np.float64)
    return x - x.max() - np.log(np.exp(x - x.max()).sum())


def check_cached_logits(cfg, eng, reqs, rows):
    """The last decode step's logits, read through the cache, against the
    full forward pass (``T.prefill``, no cache) in float32 on the chip
    over prompt + generated tokens.  The cached path reports them as the
    logprob plane: the chosen token's and the top-5 log-probs."""
    from repro.models import transformer as T

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    fwd = jax.jit(lambda p, toks: T.prefill(cfg32, eng.axes, p,
                                            {"tokens": toks})[0][:, 0])
    seqs, got, ids = [], [], []
    for i in CHECKED:
        req, resp = reqs[i], rows[reqs[i].custom_id]["response"]
        toks, lp = resp["tokens"], resp["logprobs"]
        n = len(toks)
        # decode step n-1 reads toks[n-2] at position len(prompt) + n - 2
        seqs.append(req.prompt + toks[:n - 1])
        top = lp["top_logprobs"][n - 1]
        ids.append([t for t, _ in top] + [toks[n - 1]])
        got.append([v for _, v in top] + [lp["token_logprobs"][n - 1]])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(fwd(p32, eng._put(np.asarray(seqs, np.int32))))
        nbr = np.asarray(fwd(p32, eng._put(
            np.asarray([s[:-1] for s in seqs], np.int32))))
    for i, r, nb, ii, g in zip(CHECKED, ref, nbr, ids, got):
        std = float(r[:cfg.vocab_size].std())
        tol = TOL_STD * std
        err = float(np.abs(_log_softmax(r)[ii] - g).max())
        err_nb = float(np.abs(_log_softmax(nb)[ii] - g).max())
        print(f"[logits] r{i}: max_abs_err={err:.6f} ref_logit_std="
              f"{std:.6f} tol={tol:.6f} neighbour_position_err="
              f"{err_nb:.6f}", flush=True)
        if not err < tol:
            raise AssertionError(f"r{i}: cached logits off by {err} > {tol}")
        if not err_nb > tol:
            raise AssertionError(f"r{i}: tolerance {tol} cannot tell the "
                                 f"neighbouring position ({err_nb})")


def check_sampling_compiled(eng):
    """Every sampled megastep the engine ran lowers the fused-sampling
    kernel as a compiled TPU custom call exactly when its plan's tier is
    the kernel's (interpret mode would lower it to plain HLO ops and no
    ``tpu_custom_call``); the sortless and lane tiers are XLA."""
    sampled = [(key, fn) for key, fn in eng._megastep_cache.items()
               if key[1]]
    if not sampled:
        raise AssertionError("no sampled megastep ran")
    for (steps, _, lp_k, flags), fn in sampled:
        rem = eng._put(np.zeros((eng.max_active,), np.int32))
        hlo = fn.lower(eng.params, eng.cache, eng.tokens, eng.lengths, rem,
                       eng._sp_device(), eng._sample_state
                       ).compile().as_text()
        n = hlo.count("tpu_custom_call")
        print(f"[kernel] sampled megastep steps={steps} lp_k={lp_k} "
              f"tier={flags.tier} tpu_custom_call={n}", flush=True)
        if (n > 0) != (flags.tier == "kernel"):
            raise AssertionError(f"{flags.tier} tier megastep with "
                                 f"{n} compiled kernel calls")


def one_chip(cfg, seed, on_tpu):
    from repro.configs import default_sampling
    from repro.launch.serve import build_master
    from repro.runtime.api import BatchRequest
    from repro.sampling import SamplingParams

    master, (eng,) = build_master(cfg, nodes=1, max_active=MAX_ACTIVE,
                                  max_len=MAX_LEN, page_size=PAGE,
                                  seed=seed)
    prompts = _prompts(np.random.default_rng(seed),
                       [r[0] for r in REQUESTS], cfg.vocab_size)
    reqs = [BatchRequest(
        custom_id=f"r{i}", prompt=prompt, max_tokens=mt,
        sampling=(SamplingParams() if greedy
                  else default_sampling(ARCH, seed=seed + i)),
        logprobs=lp is not None, top_logprobs=lp or 0)
        for i, (prompt, (_, mt, greedy, lp)) in enumerate(
            zip(prompts, REQUESTS))]
    rows, wall = _serve(master, reqs)
    lps = [v for r in rows.values()
           for v in r["response"].get("logprobs", {}).get("token_logprobs",
                                                          [])]
    if not lps or not np.isfinite(lps).all():
        raise AssertionError("logprob plane missing or not finite")
    print(f"[serve] requests={len(reqs)} completed failed=0 "
          f"tokens={sum(r.max_tokens for r in reqs)} wall_s={wall:.3f} "
          f"decode_steps={eng.decode_steps} "
          f"d2h_transfers={eng.d2h_transfers} "
          f"prefill_tokens={eng.prefill_tokens}", flush=True)
    if on_tpu:
        check_sampling_compiled(eng)
    check_cached_logits(cfg, eng, reqs, rows)


def four_chips(cfg, seed, devices):
    """Four engines, one per chip, under one BatchMaster; then the same
    greedy requests on one engine on one chip: tokens must match."""
    from repro.launch.serve import build_master
    from repro.runtime.api import BatchRequest

    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, have "
                           f"{len(devices)}")
    prompts = _prompts(np.random.default_rng(seed),
                       [n for n, _ in FOUR_REQUESTS], cfg.vocab_size)
    reqs = [BatchRequest(custom_id=f"g{i}", prompt=p, max_tokens=mt)
            for i, (p, (_, mt)) in enumerate(zip(prompts, FOUR_REQUESTS))]
    runs = {}
    for n in (4, 1):
        master, engines = build_master(cfg, nodes=n, max_active=FOUR_ACTIVE,
                                       max_len=MAX_LEN, page_size=PAGE,
                                       seed=seed, devices=devices[:n],
                                       device_pages=FOUR_PAGES)
        rows, wall = _serve(master, reqs)
        runs[n] = rows
        for e in engines:
            owned = jax.tree.leaves((e.params, e.cache, e.tokens, e.lengths,
                                     e._sample_state))
            if any(a.devices() != {e.device} for a in owned):
                raise AssertionError(f"node{e.node_id}: array off "
                                     f"{e.device}")
        placed = sorted(str(e.device) for e in engines)
        migrates = sum(e.stats.counts["migrate"] for e in engines)
        kv_bytes = sum(e.stats.bytes_moved["migrate"] for e in engines)
        print(f"[{n}-engine] devices={placed} wall_s={wall:.3f} "
              f"migrates={migrates} migrated_kv_bytes={kv_bytes}",
              flush=True)
        if n == 4:
            if len(set(placed)) != 4:
                raise AssertionError(f"engines share devices: {placed}")
            if migrates < 1 or kv_bytes == 0:
                raise AssertionError("no MIGRATE carried KV across chips")
    diff = [r.custom_id for r in reqs
            if runs[4][r.custom_id]["response"]["tokens"]
            != runs[1][r.custom_id]["response"]["tokens"]]
    if diff:
        raise AssertionError(f"greedy tokens differ 4 vs 1 engine: {diff}")
    print(f"[four-chips] {len(reqs)} greedy requests: tokens identical on "
          f"4 engines and on 1", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-engine phase and its "
                         "one-engine comparison")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config; the CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] {devices} kind={dev.device_kind} count={len(devices)}",
          flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1

    from repro.configs import get_config, reduced_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[cache] {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    cfg = reduced_config(ARCH) if args.reduced else get_config(ARCH)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(cfg, args.seed, devices)
    else:
        one_chip(cfg, args.seed, on_tpu)
    stats = dev.memory_stats() or {}
    print(f"[run] total_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.seconds:.3f} programs={clock.programs} "
          f"cache_hits={clock.cache_hits} peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    if args.reduced:
        print("[reduced] rehearsal passed; no result line", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
