"""The harness's entry: it refuses to report without a chip, and
``BENCHMARK.json`` names only what the harness can find by name."""
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_no_chip_no_result(bench):
    """On the CPU the run exits nonzero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = bench["workloads"][0]["name"]
    p = subprocess.run([sys.executable, os.path.join(HERE, "run.py"),
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_every_name_is_found(bench):
    """Each cell's configuration file, mix, limits and model module, and
    each metric's reader, exist under the name the harness looks for."""
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        c = configs[w["config"]]
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for path in (f"traffic/{w['traffic']}.json",
                     f"limits/{w['name']}.json",
                     f"models/{cfg['model']}.py"):
            assert os.path.isfile(os.path.join(HERE, path)), path
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py")), m["name"]


def test_names_and_units(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_harness_names_no_cell():
    """The harness's code names no cell, configuration or metric: those
    live in data files and per-metric readers."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = {x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]}
    for fname in ("run.py", "drive.py", "workload.py", "check.py",
                  "trace_reduce.py"):
        with open(os.path.join(HERE, fname)) as f:
            text = f.read()
        for n in names:
            assert not re.search(rf"[\"']{re.escape(n)}[\"']", text), \
                (fname, n)


@pytest.mark.parametrize("elapsed,jobs,seconds,more", [
    (0.0, 0, 0.0, True),        # the first job always runs
    (27.0, 1, 51.0, False),     # a second 27 s job would end at 54 s
    (25.0, 1, 51.0, True),      # ... and a second 25 s job at 50 s
    (36.0, 2, 51.0, False),
    (30.0, 2, 45.0, True),
])
def test_window_runs_whole_jobs_that_end_by_the_deadline(elapsed, jobs,
                                                         seconds, more):
    sys.path.insert(0, HERE)
    import run as R
    assert R.another_job(elapsed, jobs, seconds) is more
