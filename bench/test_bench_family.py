"""A cell's least-work count and counters come from its model family:
the dense family's count equals ``flops.py``'s hand-worked numbers, a
family added as new files alone moves ``decode_roofline``, ``step_mfu``
and the counters, the harness reads no architecture's shapes, and the
stretch's context-length histogram adds up to its ``context_sum``."""
import glob
import json
import os
import re
import shutil
import textwrap
from collections import Counter
from types import SimpleNamespace

import pytest

import drive
import run as R
import trace_reduce as tr
from conftest import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ("qwen2_0_5b.longtail_job", "qwen2_0_5b.rollout_job")
SEED = 2 ** 33 + 41         # wider than 32 bits, as the driver's are
READERS = [{"name": "decode_roofline", "unit": "%"},
           {"name": "step_mfu", "unit": "%"}]

# a family with a count unlike the dense block's, and counters of its own
STUB = textwrap.dedent('''
    import flops

    COUNTERS = ("expert_rows", "dropped_rows")


    def program_config(cfg, base):
        return base


    def program_weights(cfg, seed, device):
        return {}


    class Reference:
        def __init__(self, cfg, seed, device, max_len, control=False):
            pass


    def work(cfg):
        width = cfg["hidden_size"]

        def count(st):
            # a windowed family: attention over min(context, 128)
            windowed = sum(min(n, 128) * k
                           for n, k in st.context_hist.items())
            return flops.Count(
                decode=(width * st.decode_tokens + 4 * windowed,
                        7 * int(st.counters["decode_steps"]) + windowed),
                prefill=(width * sum(st.prefill_lens), 0))
        return count
''')


def _stretch():
    """One decode step of two slots attending to 100 and 300 positions,
    and one 3-position prompt."""
    return SimpleNamespace(decode_tokens=2, context_sum=400,
                           context_hist=Counter({100: 1, 300: 1}),
                           prefill_lens=[3], counters={"decode_steps": 1.0})


def _trace():
    return tr.TraceSummary(window_s=2.0, busy_s=1.0, devices=1,
                           programs={"jit__megastep": (0.5, 1)}, ops={})


def test_llama_work_is_the_hand_worked_dense_count():
    """``test_bench_flops``'s numbers at qwen2_0_5b's shapes, exactly."""
    cell = R.Cell.load(R.ROOT, CELLS[0])
    c = cell.model.work(cell.config)(_stretch())
    assert c.decode == (2010251264, 988065536 + 12288 * 402)
    assert c.prefill[0] == (2 * 3 * 24 * 14909440 + 2 * 136134656
                            + 86016 * 6)
    assert c.prefill[1] == 12288 * 3      # no batched call is counted


@pytest.fixture
def stub_root(tmp_path):
    """A checkout with a configuration of a new family and its cell,
    added as new files beside copies of the existing ones."""
    shutil.copytree(HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "stub_cfg", "source": "test",
                             "file": "bench/configs/stub_cfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "stub_cfg.longtail_job",
                               "config": "stub_cfg",
                               "traffic": "longtail_job", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = tmp_path / "bench"
    (b / "models" / "stub.py").write_text(STUB)
    (b / "configs" / "stub_cfg.json").write_text(json.dumps(
        {"name": "stub_cfg", "model": "stub", "reduced": [],
         "hidden_size": 1000}))
    shutil.copy(b / "limits" / f"{CELLS[0]}.json",
                b / "limits" / "stub_cfg.longtail_job.json")
    return str(tmp_path)


def test_new_family_moves_the_readers_through_work_alone(stub_root,
                                                          cpu_peaks):
    dense = R.Cell.load(R.ROOT, CELLS[0])
    stub = R.Cell.load(stub_root, "stub_cfg.longtail_job")
    assert {"decode_roofline", "step_mfu"} <= {m["name"]
                                               for m in stub.per_layer}
    read = lambda cell: R.read_metrics(READERS, R.context(
        cell, trace=_trace(), stretch=_stretch(), peaks=cpu_peaks, chips=1))
    got, base = read(stub), read(dense)
    # the stub's count: 1000 * 2 + 4 * (100 + 128) FLOPs, 7 + 228 bytes
    # for the decode step; 1000 * 3 FLOPs for the prompt
    least = max(2912 / cpu_peaks.flops, 235 / cpu_peaks.hbm_bw)
    assert got["decode_roofline"]["value"] == 100.0 * least / 0.5
    assert got["step_mfu"]["value"] == 100.0 * (2912 + 3000) / (
        2.0 * cpu_peaks.flops)
    for name in ("decode_roofline", "step_mfu"):
        assert got[name]["value"] != base[name]["value"]


def test_new_family_counters_reach_the_run(stub_root):
    """The family's ``COUNTERS`` are summed beside the base list, a
    dict-valued one per key; an engine without one counts 0."""
    stub = R.Cell.load(stub_root, "stub_cfg.longtail_job")
    engines = [
        SimpleNamespace(decode_steps=3, install_s=0.25, slots_installed=4,
                        sample_tier_pages={"lanes": 2, "sortless": 1},
                        expert_rows={"e0": 5, "e1": 7}, dropped_rows=1),
        SimpleNamespace(decode_steps=2, install_s=0.5, slots_installed=1,
                        sample_tier_pages={"lanes": 3, "sortless": 0},
                        expert_rows={"e0": 1, "e2": 2})]
    got = drive.sum_counters(engines, drive.counter_names(stub.model))
    assert got == {
        "decode_steps": 5.0, "prefill_tokens": 0.0,
        "prefill_tokens_saved": 0.0, "sync_wait_s": 0.0,
        "d2h_transfers": 0.0, "install_s": 0.75, "slots_installed": 5.0,
        "jit_builds": 0.0, "jit_build_s": 0.0,
        "sample_tier_pages.lanes": 5.0, "sample_tier_pages.sortless": 1.0,
        "expert_rows.e0": 6.0, "expert_rows.e1": 7.0,
        "expert_rows.e2": 2.0, "dropped_rows": 1.0}
    assert R._delta({"a": 1.0}, {"a": 3.0, "b": 2.0}) == {"a": 2.0,
                                                         "b": 2.0}


def test_harness_reads_no_shapes():
    """Only model families read an architecture's shapes: the harness
    and the readers name no dense-block key and build no ``Dims``."""
    shape_keys = ("hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_hidden_layers", "head_dim")
    files = [os.path.join(HERE, f) for f in ("run.py", "drive.py")] + \
        sorted(glob.glob(os.path.join(HERE, "metrics", "*.py")))
    for path in files:
        with open(path) as f:
            text = f.read()
        assert "flops.dims" not in text and "ctx.dims" not in text, path
        assert not re.search(r"\bdims\(", text), path
        for k in shape_keys:
            assert k not in text, (path, k)


@pytest.fixture(scope="module", params=CELLS)
def traced_window(request, cpu, tmp_path_factory):
    cell = tiny(R.Cell.load(R.ROOT, request.param))
    cell.mix["trace"]["seconds"] = 600.0    # the whole job, decode too
    system = drive.System(cell.config, cell.model, cpu, SEED)
    tally = drive.Tally()
    tracer = R.Tracer(True, system, tally,
                      str(tmp_path_factory.mktemp("trace")))
    R._traffic(cell, system, SEED, 0.5, tally, tracer, 1)
    counters = system.counters()
    system.close()
    return tally, tracer.stretch(), counters


def test_context_hist_sums_to_context_sum(traced_window):
    tally, st, _ = traced_window
    for part in (tally, st):
        hist = part.context_hist
        assert sum(n * k for n, k in hist.items()) == part.context_sum
        assert sum(hist.values()) == part.decode_tokens
    assert st.decode_tokens > 0


def test_run_counters_have_the_base_list(traced_window):
    from repro.sampling import SAMPLE_TIERS
    _, st, counters = traced_window
    scalar = [k for k in drive.BASE_COUNTERS if k != "sample_tier_pages"]
    want = set(scalar) | {f"sample_tier_pages.{t}" for t in SAMPLE_TIERS}
    assert set(counters) == want
    assert set(st.counters) == want
    assert counters["decode_steps"] > 0 and counters["slots_installed"] > 0
