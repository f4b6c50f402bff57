"""A whole run of a cell, minus the look for a chip, at a CPU test's
size: sound, it comes out correct; with the timed path broken
underneath, it comes out not correct.

The faults a serving cell can have: a token altered where the decode
step produces it, and a decode step that returns its state (the KV
cache) unchanged.  (Leaving half of a batch out of a mean is a training
fault, and the exchange between chips exists only on four chips.)
"""
import time

import jax.numpy as jnp
import pytest

import run as R
from conftest import tiny

CELL = "qwen2_0_5b.longtail_job"
SEED = 2 ** 33 + 17         # wider than 32 bits, as the driver's are


def _run(cell, cpu, cpu_peaks):
    return R.run(cell, SEED, 0.5, False, cpu, cpu_peaks,
                 time.perf_counter(), log=lambda s: None)


@pytest.fixture(scope="module")
def cell():
    return tiny(R.Cell.load(R.ROOT, CELL))


def test_sound_run_is_correct(cell, cpu, cpu_peaks):
    res = _run(cell, cpu, cpu_peaks)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert set(res["check"]) == set(cell.limits["limits"])


def _altered_token(orig):
    """The decode page's token block with step 0's tokens shifted by one
    in every slot, after the step produced them."""
    def decode_page(*a, **kw):
        out = orig(*a, **kw)
        block = out[0]
        if block.ndim == 3:             # the packed log-prob plane
            block = block.at[0, :, 0].add(1)
        else:
            block = block.at[0, :].add(1)
        return (block,) + tuple(out[1:])
    return decode_page


def _state_unchanged(orig):
    """A decode step that hands back the KV cache it was given."""
    def decode_step_logits(cfg, axes, params, cache, tokens, lengths,
                           **kw):
        logits, _ = orig(cfg, axes, params, cache, tokens, lengths, **kw)
        return logits, cache
    return decode_step_logits


FAULTS = {"token_altered": ("decode_page", _altered_token),
          "state_unchanged": ("decode_step_logits", _state_unchanged)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(cell, cpu, cpu_peaks, monkeypatch,
                                    fault):
    from repro.models import transformer as T
    name, make = FAULTS[fault]
    monkeypatch.setattr(T, name, make(getattr(T, name)))
    res = _run(cell, cpu, cpu_peaks)
    assert not res["correct"], res["check"]
    assert any(v["value"] is not None and v["value"] > v["limit"]
               for v in res["check"].values()), res["check"]


def test_block_plane_shape_is_as_patched():
    """The token fault edits column 0 of the plane: the token lane."""
    from repro.models import transformer as T
    plane = T.pack_logprob_block(jnp.array([3, 4], jnp.int32),
                                 jnp.zeros((2, 16)), 2)
    assert plane.shape == (2, 6) and int(plane[1, 0]) == 4
