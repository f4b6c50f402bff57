"""Whether what the timed path served is correct: a sample of the
requests the window finished, compared with the plain float32 reference.

Two numbers, each a worst case over every served token of the sample:

- ``greedy_gap``: over the greedy requests, the largest gap by which a
  served token's reference logit lies below the reference's best logit
  at that position.  A decoder that is right up to rounding serves the
  reference's argmax, or a token within rounding of it.
- ``logprob_err``: over the requests that asked for log-probs, the
  largest difference between a served token's reported log-prob and the
  reference's log-softmax of that token.

The reference is teacher-forced on each prompt plus its served tokens,
so both numbers judge every position of the cached decode, the prefill
that produced the first token, and (``logprob_err``) the log-prob plane
of the greedy and sampled plans alike.

The control puts the reference in the program's place at the next
precision below the configuration's bf16 (weights rounded to fp8); its
readings are the same two numbers, where the "served" token of a greedy
position is the one the control ranks first, and ``verdict`` has to
judge them not correct.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def sample(done: Sequence, rng: np.random.Generator, count: int) -> List:
    """``count`` finished requests drawn by ``rng``, always with the
    longest greedy one and the longest one that carries log-probs."""
    done = list(done)
    if len(done) <= count:
        return done
    picked = []
    for want in (lambda d: d.request.greedy, lambda d: d.request.logprobs):
        pool = [d for d in done
                if want(d) and all(d is not p for p in picked)]
        if pool:
            picked.append(max(pool, key=lambda d: len(d.tokens)))
    rest = [d for d in done if all(d is not p for p in picked)]
    for i in rng.permutation(len(rest))[: count - len(picked)]:
        picked.append(rest[i])
    return picked


def _positions(d):
    """The served sequence and, for each served token j, the position t
    whose logits predict it (the last prompt position for j = 0)."""
    seq = list(d.request.prompt) + list(d.tokens)
    p = len(d.request.prompt)
    return seq, np.arange(p - 1, p - 1 + len(d.tokens))


def readings(ref, picked: Sequence, control=None):
    """The two numbers for the program's served tokens; with a control
    reference, the pair (program's numbers, control's numbers), the
    control's under the same names, so that ``verdict`` judges both."""
    gaps, errs, cgaps, cerrs = [], [], [], []
    for d in picked:
        seq, pos = _positions(d)
        targets = np.zeros(len(seq), np.int32)
        targets[pos] = d.tokens
        probes = None
        if control is not None:
            cs = control.stats(seq, targets)
            probes = cs["argmax"]
        rs = ref.stats(seq, targets, probes)
        ref_lp = rs["at_target"][pos] - rs["lse"][pos]
        if d.request.greedy:
            gaps.append(float(np.max(rs["best"][pos] - rs["at_target"][pos])))
            if control is not None:
                cgaps.append(float(np.max(rs["best"][pos]
                                          - rs["at_probe"][pos])))
        if d.logprobs is not None:
            errs.append(float(np.max(np.abs(np.asarray(d.logprobs)
                                            - ref_lp))))
            if control is not None:
                c_lp = cs["at_target"][pos] - cs["lse"][pos]
                cerrs.append(float(np.max(np.abs(c_lp - ref_lp))))
    numbers = _worst(gaps, errs)
    if control is None:
        return numbers
    return numbers, _worst(cgaps, cerrs)


def _worst(gaps, errs) -> Dict[str, float]:
    out = {}
    if gaps:
        out["greedy_gap"] = max(gaps)
    if errs:
        out["logprob_err"] = max(errs)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, lines): every number at or under its limit.  A number
    with no limit, or a limit whose number the sample could not read,
    is a failure: the check must judge what it was set for."""
    ok = set(numbers) == set(limits)
    lines = []
    for name in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(name), limits.get(name)
        good = v is not None and lim is not None and v <= lim
        ok = ok and good
        lines.append({"name": name, "value": v, "limit": lim})
    return ok, lines
