"""Compile-only checks against a described TPU v5e (no chip needed).

The installed TPU compiler compiles for a topology that is described, not
attached, and refuses what the chip would refuse: block shapes off the
(8, 128) tile, more VMEM than a kernel may use, a program that does not
fit HBM.  Interpret-mode tests cannot see any of that.  These tests
compile the serving path's kernels and megasteps at qwen2_0_5b widths
(depth cut to 2 layers for the megasteps) from shapes alone.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process may hold the TPU library, and
every test worker imports this file.
"""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import sampling as smp
from repro.configs import get_config
from repro.kernels.fused_sampling.ops import fused_sample
from repro.models import transformer as T
from repro.models.api import MeshAxes

QWEN_V = 151936


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("park", [True, False])
@pytest.mark.parametrize("lp_k", [0, 5])
@pytest.mark.parametrize("B", [8, 64])
def test_fused_sample_compiles_for_v5e(one_chip, B, lp_k, park):
    """The fused-sampling kernel at qwen2's vocabulary compiles to a TPU
    custom call, parked or streamed, with and without logprob lanes."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    args = (f32(B, QWEN_V), f32(B, QWEN_V),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
            f32(B), f32(B)) + ((f32(B, QWEN_V),) if lp_k else ())
    compiled = jax.jit(lambda *a: fused_sample(
        *a, lp_k=lp_k, with_lanes=lp_k > 0, park_vmem=park)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The sampled plans of the benchmark's mixes, and one the kernel serves:
# rollout's temperature-1 rows (sortless, kc -1), longtail's Instruct
# card (top-k 20, so the lane tier at kc 32) and a top-p-only row (the
# full-sort tier, kc 0).
PLANS = {
    "rollout": [smp.SamplingParams(temperature=1.0, seed=1),
                smp.SamplingParams()],
    "longtail": [smp.SamplingParams(temperature=0.7, top_k=20, top_p=0.8,
                                    repetition_penalty=1.1, seed=1),
                 smp.SamplingParams()],
    "top_p": [smp.SamplingParams(temperature=0.8, top_p=0.9, seed=1)],
}
PLAN_KC = {"rollout": -1, "longtail": 32, "top_p": 0}


@pytest.mark.parametrize("sampled", [False, "rollout", "longtail", "top_p"])
def test_decode_page_compiles_for_v5e(one_chip, sampled, monkeypatch):
    """The fused decode megastep at qwen2_0_5b widths (2 layers): greedy,
    and sampled with logprob lanes under the plan ``flags_for`` makes on
    a TPU.  The filter + draw is the compiled kernel for the full-sort
    tier alone; the sortless and lane tiers are XLA."""
    cfg = dataclasses.replace(get_config("qwen2_0_5b"), num_layers=2)
    axes = MeshAxes(batch=("data",), model="model")
    B, S = 8, 256
    V = T.padded_vocab(cfg)
    params = _sds(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    cache = _sds(jax.eval_shape(lambda: T.init_cache(cfg, B, S)), one_chip)
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    args = (params, cache, vec, vec, vec)
    if sampled:
        rows = (PLANS[sampled] * B)[:B]
        sp = {k: v for k, v in smp.pack_params(rows, list(range(B))).items()
              if k != "seed"}
        state = {"base_key": np.zeros((B, 2), np.uint32),
                 "gen_count": np.zeros((B,), np.int32),
                 "counts": np.zeros((B, V), np.int32),
                 "prompt_counts": np.zeros((B, V), np.int32)}
        # the plan as on a TPU: this process's JAX backend is the CPU
        monkeypatch.setattr(importlib.import_module("repro.sampling.sample"),
                            "default_backend", lambda: "pallas")
        flags = smp.flags_for(rows, V)
        assert flags.kc == PLAN_KC[sampled]
        args += (_sds(sp, one_chip), _sds(state, one_chip))

        def step(p, c, t, l, r, s, st):
            return T.decode_page(cfg, axes, p, c, t, l, r, 4,
                                 sampling=(s, st), lp_k=5, flags=flags)
    else:
        def step(p, c, t, l, r):
            return T.decode_page(cfg, axes, p, c, t, l, r, 4)
    text = jax.jit(step, donate_argnums=(1,)).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == (sampled == "top_p")
    # the lane tier's top-k stays XLA's TopK, never a sort of the row
    assert " sort(" not in text
