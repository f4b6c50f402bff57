"""Serving driver: a BatchMaster over NodeEngines for one (arch, config).

``build_master`` is the one construction of the serving path — used by
this CLI and by ``chip_smoke.py``.  Engines go round-robin over the
local devices (``jax.devices()``), so ``--nodes 4`` on a four-chip host
puts one engine on each chip.  On a TPU the published config runs as
is; on a CPU pass ``--reduced`` (a tiny same-family config) with
``JAX_PLATFORMS=cpu``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \\
        --arch llama3_2_1b --reduced
"""
from __future__ import annotations

import argparse
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.scheduler import SchedulerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import ModelConfig
from repro.runtime.api import BatchMaster, BatchRequest
from repro.runtime.engine import NodeEngine


def build_master(cfg: ModelConfig, *, nodes: int, max_active: int,
                 max_len: int, page_size: int, seed: int = 0,
                 devices: Optional[Sequence] = None,
                 **engine_kw) -> Tuple[BatchMaster, List[NodeEngine]]:
    """``nodes`` engines (weights from ``seed``; engine i on
    ``devices[i % len(devices)]``, default ``jax.devices()``; further
    NodeEngine arguments in ``engine_kw``) under one BatchMaster."""
    devices = list(devices or jax.devices())
    engines = [NodeEngine(cfg, node_id=i, max_active=max_active,
                          max_len=max_len, page_size=page_size, seed=seed,
                          device=devices[i % len(devices)], **engine_kw)
               for i in range(nodes)]
    master = BatchMaster(engines, SchedulerConfig(page_size=page_size))
    return master, engines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-active", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    master, engines = build_master(cfg, nodes=args.nodes,
                                   max_active=args.max_active, max_len=256,
                                   page_size=args.page_size)
    rng = np.random.default_rng(0)
    reqs = [BatchRequest(custom_id=f"r{i}",
                         prompt=list(rng.integers(2, cfg.vocab_size, 8)),
                         max_tokens=int(rng.integers(4, 48)))
            for i in range(args.requests)]
    bid = master.submit(reqs)
    bo = master.run(bid)
    print(f"{bo.id}: {bo.request_counts} BCT={bo.bct_s:.2f}s")
    for i, e in enumerate(engines):
        print(f"node{i}: {e.stats.counts} decode_steps={e.decode_steps} "
              f"device={e.device}")


if __name__ == "__main__":
    main()
