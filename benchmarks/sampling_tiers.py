"""Device time of one sampled decode step's sampling, by path: the
fused-sampling kernel against XLA's sortless tier and its top-k lane tier.

    python benchmarks/sampling_tiers.py [--rows 32] [--vocab 151936]
        [--iters 50] [--out PATH]

Each case is ``repro.sampling.sample_step`` (draw, state advance and
log-prob lanes) jitted alone on (rows, vocab) f32 logits, with the plan
(`SampleFlags`) that case names; the kernel cases run only on a TPU.  A
case is called ``--iters`` times back to back and waited for once, so
each number is device time per call plus what dispatch does not hide.
``top_k.*`` cases time one ``lax.top_k`` over the rows (``two_stage``:
``top_k`` per vocabulary chunk, then over the candidates).  Prints one
JSON line per case, ``{"case", "ms", "rows", "vocab", "device"}``, and
writes them all to ``--out`` when given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):      # `python benchmarks/sampling_tiers.py`
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import sampling as smp
from repro.sampling.sample import _gumbel_rows

LANE_KCS = (8, 32, 128, 512, 4096)


def _plan_rows(B: int, greedy: int, **sampled):
    """pack_params rows: ``greedy`` greedy rows, the rest ``sampled``."""
    sps = [smp.SamplingParams() if i < greedy else
           smp.SamplingParams(seed=i, **sampled) for i in range(B)]
    sp = smp.pack_params(sps, list(range(B)))
    return {k: jnp.asarray(v) for k, v in sp.items() if k != "seed"}


def _state(B: int, V: int, rng):
    prompt = np.zeros((B, V), np.int32)
    prompt[np.arange(B)[:, None], rng.integers(0, V, (B, 256))] = 1
    return {"base_key": jnp.asarray(smp.base_keys_host(np.arange(B))),
            "gen_count": jnp.zeros((B,), jnp.int32),
            "counts": jnp.zeros((B, V), jnp.int32),
            "prompt_counts": jnp.asarray(prompt)}


def two_stage_top_k(x, k: int, chunk: int):
    """``lax.top_k(x, k)`` as top-k per ``chunk`` of the last axis, then
    top-k over the candidates: the same values and ids, ties to the
    lowest index (candidates keep chunk order, and each chunk keeps its
    own ties in index order)."""
    B, V = x.shape
    n = -(-V // chunk)
    xp = jnp.pad(x, ((0, 0), (0, n * chunk - V)), constant_values=-jnp.inf)
    v, i = jax.lax.top_k(xp.reshape(B, n, chunk), k)
    i = i + (jnp.arange(n, dtype=jnp.int32) * chunk)[None, :, None]
    v2, j = jax.lax.top_k(v.reshape(B, n * k), k)
    return v2, jnp.take_along_axis(i.reshape(B, n * k), j, axis=1)


def cases(B: int, V: int, on_tpu: bool):
    """name -> (jitted fn, args).  Rollout's plan: temperature 1, chosen
    log-probs, one greedy row in 16, no penalty (sortless, kc -1).
    Longtail's plan: the Instruct card (top-k 20, top-p 0.8, repetition
    1.1) with top-5 lanes, three greedy rows in four."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0.0, 3.0, (B, V)), jnp.float32)
    remaining = jnp.full((B,), 64, jnp.int32)
    state = _state(B, V, rng)
    roll = _plan_rows(B, max(B // 16, 1), temperature=1.0)
    card = _plan_rows(B, 3 * B // 4, temperature=0.7, top_k=20, top_p=0.8,
                      repetition_penalty=1.1)
    topp = _plan_rows(B, 0, temperature=0.8, top_p=0.9)

    def step(flags, lp_k):
        return jax.jit(lambda lg, r, st, sp: smp.sample_step(
            lg, r, st, sp, flags, lp_k=lp_k))

    out = {}
    plans = [("sortless", roll, 0,
              dict(pen=False, kc=-1, mixed=True, stops=False)),
             ("full_sort", topp, 0,
              dict(pen=False, kc=0, mixed=False, stops=False))]
    plans += [(f"lanes.kc{kc}", card, 5,
               dict(pen=True, kc=kc, mixed=True, stops=False))
              for kc in LANE_KCS]
    for name, sp, lp_k, kw in plans:
        args = (logits, remaining, state, sp)
        out[f"xla.{name}"] = (step(smp.SampleFlags(backend="xla", **kw),
                                   lp_k), args)
        if on_tpu and name in ("sortless", "full_sort", "lanes.kc32"):
            out[f"kernel.{name}"] = (step(smp.SampleFlags(
                backend="pallas", **kw), lp_k), args)
    for kc in (5, 32):
        out[f"top_k.full.k{kc}"] = (jax.jit(
            lambda x, kc=kc: jax.lax.top_k(x, kc)), (logits,))
        for chunk in (1024, 4096):
            out[f"top_k.two_stage.c{chunk}.k{kc}"] = (jax.jit(
                lambda x, kc=kc, c=chunk: two_stage_top_k(x, kc, c)),
                (logits,))
    out["gumbel_rows"] = (jax.jit(lambda k: _gumbel_rows(k, V)),
                          (state["base_key"],))
    out["log_softmax"] = (jax.jit(lambda x: jax.nn.log_softmax(x, -1)),
                          (logits,))
    return out


def time_case(fn, args, iters: int) -> float:
    jax.block_until_ready(fn(*args))              # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=151936)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind}
    rows = []
    todo = cases(args.rows, args.vocab, d.platform == "tpu")
    for name, (fn, a) in todo.items():
        row = {"case": name, "ms": time_case(fn, a, args.iters),
               "rows": args.rows, "vocab": args.vocab, "device": device}
        if name.startswith("top_k.two_stage."):
            full = todo["top_k.full." + name.split(".")[-1]]
            want, got = full[0](*full[1]), fn(*a)
            row["exact"] = all(bool(jnp.array_equal(w, g))
                               for w, g in zip(want, got))
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
