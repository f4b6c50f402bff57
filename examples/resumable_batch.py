"""Crash-resumable batch job (§5.6): SIGKILL a run mid-batch, resume it,
get byte-identical output with zero recompute of finished sequences.

The write-ahead ``JobLedger`` journals every finished request the moment
its ``SeqFinishedEvent`` comes off the ``BatchMaster`` stream; a rerun
with the same ledger skips the journaled requests and decodes only the
remainder.  Because the runtime's decode is deterministic (greedy +
token-addressable fold_in sampling), the stitched output equals the
uninterrupted run byte for byte.

    PYTHONPATH=src python examples/resumable_batch.py              # demo
    PYTHONPATH=src python examples/resumable_batch.py --selftest   # CI smoke

``--selftest`` runs the full kill-and-resume protocol in subprocesses:
an uninterrupted reference run, a run SIGKILLed after K finishes (real
signal 9 — no atexit, no flush), and a resumed run; it asserts the
resumed output file equals the reference byte for byte and that the
resume skipped every journaled request.  A chip belongs to one process
at a time: the parent only launches and touches no device array, so each
child in turn can hold the chip.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import reduced_config
from repro.runtime.api import BatchMaster, BatchRequest
from repro.runtime.engine import NodeEngine
from repro.runtime.ledger import JobLedger, run_resumable
from repro.sampling import SamplingParams

N_REQ = 8
MAX_TOKENS = 12


def make_requests():
    cfg = reduced_config("phi3_5_moe")
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(N_REQ):
        prompt = [int(t) for t in rng.integers(2, cfg.vocab_size,
                                               int(rng.integers(4, 10)))]
        # mix greedy and seeded-sampled rows: both must be reproducible
        sp = (SamplingParams() if i % 2 == 0
              else SamplingParams(temperature=0.8, top_k=40, seed=100 + i))
        reqs.append(BatchRequest(custom_id=f"req-{i}", prompt=prompt,
                                 max_tokens=MAX_TOKENS, sampling=sp))
    return reqs


def make_master():
    cfg = reduced_config("phi3_5_moe")
    eng = NodeEngine(cfg, node_id=0, max_active=4, max_len=128,
                     page_size=16, seed=0)
    return BatchMaster([eng])


def worker(ledger: str, out: str, kill_after: int):
    """One resumable pass.  With ``kill_after`` > 0, SIGKILL ourselves the
    instant the K-th output row commits to the ledger — a real crash in
    the middle of the batch, after durable progress exists."""
    def maybe_kill(_cid, n_done):
        if kill_after and n_done >= kill_after:
            os.kill(os.getpid(), signal.SIGKILL)

    res = run_resumable(make_master(), make_requests(), ledger,
                        on_output=maybe_kill)
    with open(out, "w") as f:
        f.write("\n".join(json.dumps(r) for r in res.rows) + "\n")
    print(f"[worker] resumed={res.resumed} computed={res.computed} "
          f"rows={len(res.rows)}")
    return res


def selftest():
    py = sys.executable
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    me = os.path.abspath(__file__)
    with tempfile.TemporaryDirectory() as td:
        ref_out = os.path.join(td, "ref.jsonl")
        led = os.path.join(td, "job.ledger.jsonl")
        res_out = os.path.join(td, "resumed.jsonl")

        def run(args):
            return subprocess.run([py, me] + args, env=env,
                                  capture_output=True, text=True)

        # 1) uninterrupted reference (its own ledger)
        r = run(["--worker", "--ledger", os.path.join(td, "ref.ledger"),
                 "--out", ref_out])
        assert r.returncode == 0, r.stderr
        # 2) crash mid-batch: SIGKILL after 3 committed rows
        r = run(["--worker", "--ledger", led, "--out", res_out,
                 "--kill-after", "3"])
        assert r.returncode == -signal.SIGKILL, \
            f"expected SIGKILL, got rc={r.returncode}\n{r.stderr}"
        assert not os.path.exists(res_out), "killed run must not emit output"
        survivors = JobLedger(led)
        survivors._load()
        n_journaled = len(survivors.finished)
        assert n_journaled >= 3, f"ledger lost rows: {n_journaled}"
        # 3) resume: skips journaled rows, decodes the rest
        r = run(["--worker", "--ledger", led, "--out", res_out])
        assert r.returncode == 0, r.stderr
        assert f"resumed={n_journaled}" in r.stdout, \
            f"resume recomputed finished work:\n{r.stdout}"
        with open(ref_out, "rb") as f:
            ref = f.read()
        with open(res_out, "rb") as f:
            got = f.read()
        assert got == ref, "resumed output differs from uninterrupted run"
        print(f"[selftest] PASS: killed after {n_journaled}/{N_REQ} rows, "
              f"resume skipped all {n_journaled}, output byte-identical")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--ledger", default="/tmp/resumable_batch.ledger.jsonl")
    ap.add_argument("--out", default="/tmp/resumable_batch.out.jsonl")
    ap.add_argument("--kill-after", type=int, default=0)
    args = ap.parse_args()
    if args.selftest:
        selftest()
    elif args.worker:
        worker(args.ledger, args.out, args.kill_after)
    else:
        # demo: fresh ledger, run, then rerun to show the no-op resume
        if os.path.exists(args.ledger):
            os.unlink(args.ledger)
        worker(args.ledger, args.out, 0)
        res = worker(args.ledger, args.out, 0)
        assert res.resumed == N_REQ and res.computed == 0
        print(f"[demo] second pass served all {N_REQ} rows from the ledger")


if __name__ == "__main__":
    main()
