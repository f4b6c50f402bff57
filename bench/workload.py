"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into requests.

A mix file is data only.  Its keys:

- ``mode``: how the mix drives the system (``job``, ``stream`` or
  ``rollout``; see ``drive.py``).
- ``requests``: requests in one job, or in the stream's pool; in a
  rollout, the prompts of one job (each forked ``group_size`` times).
- ``prompt``: the prompt-length distribution; ``output``: a list of
  output-length components, each with a ``share``; ``sampling``: a list
  of sampling kinds, each with a ``share``.
- ``order_seed``: fixes which prompt length, output length and sampling
  kind each request position gets.

Lengths are a fixed multiset: each component contributes ``share * n``
lengths, taken at the distribution's quantiles ``(i + 0.5) / count``,
and ``order_seed`` pairs them.  So every run of a cell does the same
work in the same order, and the run's ``--seed`` draws only the prompt
tokens and the sampling seeds.  Every prompt is cut so that prompt plus
output fits the engine's ``max_len - 1`` positions.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Spec:
    """One request position of a mix: lengths and sampling kind."""
    prompt_len: int
    max_tokens: int
    sampling: dict          # the sampling kind's entry of the mix file


@dataclass
class Request:
    """One generated request; ``group`` names its fork group in a rollout."""
    custom_id: str
    prompt: List[int]
    max_tokens: int
    sampling: dict
    seed: Optional[int]
    group: Optional[int] = None

    @property
    def greedy(self) -> bool:
        return float(self.sampling.get("temperature", 0.0)) <= 0.0

    @property
    def logprobs(self) -> bool:
        return bool(self.sampling.get("logprobs", False))


def quantiles(dist: dict, count: int) -> List[int]:
    """``count`` lengths at the distribution's quantiles (i + 0.5)/count,
    clipped to [min, max] and rounded."""
    out = []
    for i in range(count):
        p = (i + 0.5) / count
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"]
                                          * NormalDist().inv_cdf(p))
        elif dist["dist"] == "uniform":
            x = dist["min"] + p * (dist["max"] - dist["min"])
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(int(round(min(max(x, dist["min"]), dist["max"]))))
    return out


def _counts(shares: List[float], n: int) -> List[int]:
    """Split n over components by share, largest remainders first."""
    raw = [s * n for s in shares]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _mixture(components: List[dict], n: int) -> List:
    counts = _counts([c["share"] for c in components], n)
    return [c for c, k in zip(components, counts) for _ in range(k)]


def specs(mix: dict, max_len: int, n: Optional[int] = None) -> List[Spec]:
    """The mix's request positions, the same for every seed."""
    n = n or mix["requests"] * (mix.get("group_size", 1)
                                if mix["mode"] == "rollout" else 1)
    groups = n // mix.get("group_size", 1) if mix["mode"] == "rollout" else n
    order = np.random.default_rng(mix["order_seed"])
    prompts = quantiles(mix["prompt"], groups)
    outputs = []
    for comp, k in zip(mix["output"], _counts(
            [c["share"] for c in mix["output"]], n)):
        outputs += quantiles(comp, k)
    kinds = _mixture(mix["sampling"], groups)
    prompts = [prompts[i] for i in order.permutation(groups)]
    outputs = [outputs[i] for i in order.permutation(n)]
    kinds = [kinds[i] for i in order.permutation(groups)]
    per = n // groups
    out = []
    for i in range(n):
        g = i // per
        mt = outputs[i]
        pl = min(prompts[g], max_len - 1 - max(outputs[g * per:(g + 1) * per]))
        if pl < 1:
            raise ValueError(f"output {mt} leaves no room for a prompt in "
                             f"max_len {max_len}")
        out.append(Spec(pl, mt, kinds[g]))
    return out


def _seed_words(*parts: int) -> np.random.Generator:
    return np.random.default_rng([int(p) & 0xFFFFFFFFFFFFFFFF for p in parts])


def requests(mix: dict, specs_: List[Spec], vocab: int, seed: int,
             stream: int, start: int = 0) -> List[Request]:
    """Requests for positions ``start .. start+len(specs_)`` of one stream
    of a run: ``stream`` 0 is the warm-up, 1 the measured window.  The
    seed draws each prompt's tokens and each sampled request's seed;
    requests of one fork group share the prompt."""
    rng = _seed_words(seed, stream, start)
    per = mix.get("group_size", 1) if mix["mode"] == "rollout" else 1
    out, prompt = [], None
    for i, sp in enumerate(specs_):
        k = start + i
        if k % per == 0:
            prompt = [int(t) for t in rng.integers(0, vocab, sp.prompt_len)]
        greedy = float(sp.sampling.get("temperature", 0.0)) <= 0.0
        out.append(Request(
            custom_id=f"s{stream}r{k}", prompt=prompt,
            max_tokens=sp.max_tokens, sampling=sp.sampling,
            seed=None if greedy else int(rng.integers(0, 2 ** 31)),
            group=k // per if per > 1 else None))
    return out
