#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from, and the
proof that the control comes out not correct.

    python3 bench/control.py --workload <name> --seeds 101 102 ... \\
        [--out readings.jsonl]

For each seed it serves the cell's traffic at the cell's own sizes and
load (one whole job, run to its end), takes the same sample of finished
requests a benchmark run compares, and reads the check's numbers for the
program and for the control: the float32 reference put in the program's
place with its weights rounded to fp8, the precision below the
configuration's bf16.  Both sets of numbers go through the cell's own
``check.verdict`` against its limits: the program's has to come out
correct and the control's not correct, on every seed, or the command
exits 1.  One process serves all seeds, then frees the program and runs
the references.

A limit lies above the largest program reading over a dozen seeds or
more and below the smallest control reading (see PERF.md).  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse                                                 # noqa: E402
import gc                                                       # noqa: E402
import json                                                     # noqa: E402
import sys                                                      # noqa: E402

import numpy as np                                              # noqa: E402

import run as R                                                 # noqa: E402
import check                                                    # noqa: E402
import drive                                                    # noqa: E402


def serve(cell: R.Cell, system, seed: int) -> list:
    """The cell's traffic once, from ``seed``, to its end; returns the
    sample of finished requests a run with that seed would compare."""
    for e in system.engines:
        e.params = cell.model.program_weights(cell.config, seed, e.device)
    tally = drive.Tally()
    R._traffic(cell, system, seed, 0.0, tally,
               R.Tracer(False, None, None, ""), 1)
    if tally.failed:
        raise RuntimeError(f"seed {seed}: {tally.failed} requests failed")
    rng = np.random.default_rng([seed & (2 ** 64 - 1), 2])
    return check.sample(tally.done, rng, cell.limits["sample_requests"])


def readings(cell: R.Cell, seed: int, picked, device) -> dict:
    """The program's numbers and the control's, each with its verdict
    against the cell's limits, for one seed."""
    max_len = cell.config["engine"]["max_len"]
    ref = cell.model.Reference(cell.config, seed, device, max_len)
    ctl = cell.model.Reference(cell.config, seed, device, max_len,
                               control=True)
    program, control = check.readings(ref, picked, ctl)
    limits = cell.limits["limits"]
    return {"seed": seed, "requests": len(picked),
            "tokens": sum(len(d.tokens) for d in picked),
            "program": program,
            "program_correct": check.verdict(program, limits)[0],
            "control": control,
            "control_correct": check.verdict(control, limits)[0],
            "limits": limits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = R.Cell.load(R.ROOT, args.workload)
    try:
        devices = R.require_chips(int(cell.workload["chips"]))
    except R.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    R.use_compile_cache(R.CACHE_DIR)
    system = drive.System(cell.config, cell.model, devices, args.seeds[0])
    R._traffic(cell, system, args.seeds[0], 0.0, drive.Tally(),
               R.Tracer(False, None, None, ""), 0)
    served = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        served[seed] = serve(cell, system, seed)
        print(f"[serve] seed={seed} s={time.perf_counter() - t0:.1f}",
              file=sys.stderr, flush=True)
    system.close()
    del system
    gc.collect()
    rows = []
    for seed in args.seeds:
        row = readings(cell, seed, served[seed], devices[0])
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    summary = {side: {n: {"max": max(r[side][n] for r in rows),
                          "min": min(r[side][n] for r in rows)}
                      for n in rows[0][side]}
               for side in ("program", "control")}
    sound = all(r["program_correct"] and not r["control_correct"]
                for r in rows)
    print(json.dumps({"summary": summary, "program_correct_all":
                      all(r["program_correct"] for r in rows),
                      "control_correct_none":
                      not any(r["control_correct"] for r in rows)}),
          flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
