"""The control at a CPU test's size: the float32 reference put in the
program's place with fp8 weights reads worse than the program on the
same served tokens, on every number the check compares, and the cell's
own verdict judges the program correct.  (At this size the control's
readings stay under the cell's limits; at the cell's own size
``control.py`` on the chip requires the verdict to judge the control
not correct on every seed.)

(``control.py`` makes the same readings on the chip at the cell's own
size; PERF.md gives them and the limits set from them.)
"""
import time

import jax
import pytest

import check
import drive
import run as R
from conftest import tiny

SEEDS = (5, 2 ** 35 + 1)


@pytest.fixture(scope="module")
def served(cpu):
    cell = tiny(R.Cell.load(R.ROOT, "qwen2_0_5b.longtail_job"), requests=8)
    out = {}
    system = drive.System(cell.config, cell.model, cpu, SEEDS[0])
    R._traffic(cell, system, SEEDS[0], 0.0, drive.Tally(),
               R.Tracer(False, None, None, ""), 0)
    import control
    for seed in SEEDS:
        out[seed] = control.serve(cell, system, seed)
    system.close()
    return cell, out


@pytest.mark.parametrize("seed", SEEDS)
def test_control_reads_worse_than_program(served, cpu, seed):
    import control
    cell, picked = served
    t0 = time.perf_counter()
    row = control.readings(cell, seed, picked[seed], cpu[0])
    assert time.perf_counter() - t0 < 300
    print(row)
    assert set(row["control"]) == set(cell.limits["limits"])
    for name in ("greedy_gap", "logprob_err"):
        assert row["control"][name] > row["program"][name], row
    assert row["program_correct"], row
    assert row["control_correct"] == check.verdict(
        row["control"], cell.limits["limits"])[0]


def test_control_weights_differ_only_in_matrices(cpu):
    cell = tiny(R.Cell.load(R.ROOT, "qwen2_0_5b.longtail_job"))
    ref = cell.model.Reference(cell.config, 3, cpu[0], 256)
    ctl = cell.model.Reference(cell.config, 3, cpu[0], 256, control=True)
    for name in ref.w:
        same = bool(jax.numpy.array_equal(ref.w[name], ctl.w[name]))
        assert same == (name in ("norm", "ln1", "ln2", "bq", "bk", "bv")), \
            name
    assert check.verdict({"a": 1.0}, {"a": 2.0})[0]
    assert not check.verdict({"a": 3.0}, {"a": 2.0})[0]
    assert not check.verdict({}, {"a": 2.0})[0]
