"""Drives the system under test through its public entry, in one of the
modes a mix file names, and tallies what the window produced.

- ``job``: ``BatchMaster.submit`` + ``stream``, one whole job at a time,
  jobs back to back, so a window is a whole number of jobs.
- ``rollout``: ``BatchMaster.open``, then the scheduler's ``submit`` with
  ``n = group_size`` forked siblings per prompt, pumped to completion;
  jobs back to back as in ``job``.

Nothing here branches on a cell, configuration or metric name.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

import workload as wl


@dataclass
class Finished:
    request: wl.Request
    tokens: List[int]
    logprobs: Optional[List[float]]


@dataclass
class Tally:
    """What one stretch of traffic produced, from the stream's records."""
    tokens: int = 0             # output tokens emitted
    decode_tokens: int = 0      # of which produced by decode steps
    context_sum: int = 0        # sum of attended positions over those
    prefill_lens: List[int] = field(default_factory=list)  # prompts forwarded
    jobs: int = 0
    attempted: int = 0
    done: List[Finished] = field(default_factory=list)
    failed: int = 0

    def block(self, req: wl.Request, offset: int, n: int, lead: bool):
        """Record ``n`` tokens of ``req`` starting at generated index
        ``offset``.  Index 0 is the prefill's; index j >= 1 comes from a
        decode step that attends to prompt + j positions."""
        self.tokens += n
        p = len(req.prompt)
        for j in range(offset, offset + n):
            if j == 0:
                if lead:
                    self.prefill_lens.append(p)
            else:
                self.decode_tokens += 1
                self.context_sum += p + j

    def finish(self, req: wl.Request, tokens, logprobs):
        if len(tokens) != req.max_tokens:
            self.failed += 1        # no stop tokens are set: must be exact
        else:
            self.done.append(Finished(req, list(tokens), logprobs))


def sampling_params(req: wl.Request):
    from repro.sampling import SamplingParams
    s = req.sampling
    if req.greedy:
        return SamplingParams()
    return SamplingParams(
        temperature=float(s["temperature"]), top_k=int(s.get("top_k", 0)),
        top_p=float(s.get("top_p", 1.0)),
        repetition_penalty=float(s.get("repetition_penalty", 1.0)),
        seed=req.seed)


def batch_request(req: wl.Request):
    from repro.runtime.api import BatchRequest
    return BatchRequest(custom_id=req.custom_id, prompt=req.prompt,
                        max_tokens=req.max_tokens,
                        sampling=sampling_params(req),
                        logprobs=req.logprobs,
                        top_logprobs=int(req.sampling.get("top_logprobs", 0)))



class System:
    """The program under test: engines under one BatchMaster, with the
    benchmark's weights."""

    def __init__(self, cfg_file: dict, model, devices, seed: int):
        from repro.configs import get_config
        from repro.launch.serve import build_master
        eng = cfg_file["engine"]
        self.cfg = model.program_config(cfg_file,
                                        get_config(cfg_file["registry"]))
        self.master, self.engines = build_master(
            self.cfg, nodes=len(devices), max_active=eng["max_active"],
            max_len=eng["max_len"], page_size=eng["page_size"],
            devices=devices)
        for e in self.engines:
            e.params = model.program_weights(cfg_file, seed, e.device)
        jax.block_until_ready([e.params for e in self.engines])
        self.slots = sum(e.max_active for e in self.engines)

    def trace_spans(self):
        """Host spans around the engine's calls, for traced runs only."""
        names = ("decode_page", "prefill", "stage_appends", "drain_appends",
                 "_flush_pending_installs")
        for e in self.engines:
            for n in names:
                fn = getattr(e, n, None)
                if fn is not None:
                    setattr(e, n, _wrapped(fn, f"engine.{n.strip('_')}"))

    def counters(self) -> Dict[str, float]:
        keys = ("decode_steps", "prefill_tokens", "prefill_tokens_saved",
                "sync_wait_s", "d2h_transfers")
        return {k: float(sum(getattr(e, k, 0) for e in self.engines))
                for k in keys}

    def close(self):
        """Drop every reference to the program's device arrays."""
        for e in self.engines:
            for name in ("params", "cache", "tokens", "lengths",
                         "_sample_state", "_sp_dev"):
                if hasattr(e, name):
                    setattr(e, name, None)
        self.engines, self.master = [], None


def _wrapped(fn, name):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


# ------------------------------------------------------------------ modes
def run_job(system: System, reqs: List[wl.Request], tally: Tally,
            tick=None):
    """One whole job; ``tick()`` is called after every record."""
    master = system.master
    by_id = {r.custom_id: r for r in reqs}
    bid = master.submit([batch_request(r) for r in reqs])
    tally.attempted += len(reqs)
    from repro.core.events import TokenBlockEvent
    for rec in master.stream(bid):
        if tick is not None:
            tick()
        if isinstance(rec, TokenBlockEvent):
            tally.block(by_id[rec.custom_id], rec.offset, len(rec.tokens),
                        True)
    bo = master.retrieve(bid)
    for row in bo.results:
        req = by_id[row["custom_id"]]
        resp = row["response"]
        if row.get("status_code") != 200:
            tally.failed += 1
            continue
        lp = resp.get("logprobs", {}).get("token_logprobs")
        tally.finish(req, resp["tokens"], lp)
    tally.jobs += 1


def run_rollout(system: System, reqs: List[wl.Request], tally: Tally,
                tick=None):
    """One whole rollout job; ``tick()`` is called before every round."""
    from repro.core.events import SeqFinishedEvent, TokenBlockEvent
    master = system.master
    bid = master.open()
    sched = master.scheduler(bid)
    per = len([r for r in reqs if r.group == reqs[0].group])
    leads = reqs[::per]
    ids = sched.submit(
        [r.prompt for r in leads], [r.max_tokens for r in reqs],
        sampling=[sampling_params(r) for r in reqs],
        logprobs=[r.logprobs for r in reqs],
        top_logprobs=[int(r.sampling.get("top_logprobs", 0)) for r in reqs],
        n=per)
    by_seq = dict(zip(ids, reqs))
    lead_ids = {r.custom_id for r in leads}
    tally.attempted += len(reqs)
    left = set(ids)
    while left:
        if tick is not None:
            tick()
        with jax.profiler.TraceAnnotation("bench.pump"):
            recs = master.pump(bid)
        for rec in recs:
            req = by_seq.get(rec.seq_id)
            if req is None:
                continue
            if isinstance(rec, TokenBlockEvent):
                tally.block(req, rec.offset, len(rec.tokens),
                            req.custom_id in lead_ids)
            elif isinstance(rec, SeqFinishedEvent):
                co = sched.cos[rec.seq_id]
                tally.finish(req, co.generated,
                             list(co.token_logprobs) if req.logprobs
                             else None)
                left.discard(rec.seq_id)
    master.close(bid)
    tally.jobs += 1

