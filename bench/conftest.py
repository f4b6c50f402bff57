"""Shared helpers for the benchmark's CPU tests: a cell cut to a size a
test can run on the CPU (two layers, a 512-entry vocabulary, four slots
of 256 positions, short requests), with every width as published."""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# the CPU's stand-in peaks: only the reading functions use them
CPU_PEAKS = dict(flops=1e12, hbm_bw=1e11, hbm_bytes=1 << 34,
                 source="CPU test stand-in")


def tiny(cell, requests=4):
    """``cell`` cut to a CPU test's size (a copy; the cell is untouched)."""
    cell = copy.copy(cell)
    c = dict(cell.config)
    c.update(num_hidden_layers=2, vocab_size=512,
             engine={"max_active": 4, "max_len": 256, "page_size": 16})
    cell.config = c
    m = copy.deepcopy(cell.mix)
    m["requests"] = requests
    if m["mode"] == "rollout":
        m["group_size"] = 2
    m["prompt"].update(median=40, min=8, max=100)
    for o in m["output"]:
        o.update(min=4, max=min(o["max"], 40))
        if "median" in o:
            o["median"] = 16
    m["trace"] = {"skip_s": 0.0, "seconds": 1.0}
    cell.mix = m
    return cell


@pytest.fixture(scope="module")
def cpu():
    import jax
    return jax.devices("cpu")[:1]


@pytest.fixture(scope="module")
def cpu_peaks():
    from peaks import Peaks
    return Peaks(**CPU_PEAKS)
