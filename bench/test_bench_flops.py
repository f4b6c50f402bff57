"""The least-work counts of ``flops.py`` at qwen2_0_5b's shapes, against
numbers worked out by hand, and the peaks table's refusal of a chip it
does not know."""
import json
import os

import pytest

import flops
from peaks import PEAKS, peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def qwen():
    with open(os.path.join(HERE, "configs", "qwen2_0_5b.json")) as f:
        return flops.dims(json.load(f))


def test_qwen_shapes(qwen):
    # per layer: q 896*896 + k,v 2*896*128 + o 896*896 + mlp 3*896*4864
    assert qwen.layer_matmul_params == 802816 + 229376 + 802816 + 13074432
    # two norm scales of 896 and q/k/v biases of (14 + 2 + 2) * 64
    assert qwen.layer_vector_params == 1792 + 1152
    assert qwen.head_params == 896 * 151936
    # bf16: 24 layers, the final norm and the tied head, 2 bytes each
    assert qwen.weight_bytes == 2 * (24 * 14912384 + 896 + 136134656)
    # K and V, 24 layers, 2 kv heads of 64, bf16: 12 KiB a position
    assert qwen.kv_bytes_per_position == 12288
    # q.k and p.v: 2 * 64 each, 14 heads, 24 layers
    assert qwen.attn_flops_per_pair == 86016


def test_decode_counts(qwen):
    # one step, two live slots attending to 100 and 300 positions
    f, b = flops.decode(qwen, steps=1, tokens=2, context_sum=400)
    assert f == 2 * 2 * (24 * 14909440 + 136134656) + 86016 * 400
    assert f == 2010251264
    # all weights once, K/V of 400 attended positions, 2 written
    assert b == 988065536 + 12288 * 402


def test_prefill_counts(qwen):
    # one call forwarding a 3-position prompt: the head at the last
    # position only, 3 * 4 / 2 = 6 causal pairs
    f, b = flops.prefill(qwen, [3], calls=1)
    assert f == 2 * 3 * 24 * 14909440 + 2 * 136134656 + 86016 * 6
    assert b == 988065536 + 12288 * 3


def test_sampling_and_bound(qwen):
    assert flops.sampling(qwen, 32) == 32 * 151936 * 4
    assert flops.least_seconds(2e12, 1e9, 1e12, 1e9) == 2.0
    assert flops.least_seconds(1e12, 4e9, 1e12, 1e9) == 4.0


def test_decode_is_memory_bound_at_these_batches(qwen):
    # 32 slots at 1,000 positions: well under v5e's ridge of 240 FLOP/B
    f, b = flops.decode(qwen, 1, 32, 32 * 1000)
    assert f / b < 197e12 / 819e9


def test_peaks_known_and_unknown():
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
    with pytest.raises(KeyError):
        peaks_for("cpu")
