"""Jitted wrapper for the fused-sampling kernel (padding + output dict)."""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_sampling.fused_sampling import (LANES, OUT_COLS,
                                                         ROWS, TILE,
                                                         fused_sampling_tpu)
from repro.kernels.fused_sampling.ref import NEG

# VMEM budget for the parked (ROWS, V) logits block: park whenever it
# fits.  v5e's compiler accepts a 12 MiB park and refuses 14 MiB (16 MiB
# scoped VMEM, the rest holds the double-buffered input tiles, the
# (NB, 128) histograms and the binning temporaries); the cap keeps 4 MiB
# of margin.  At qwen2's vocabulary (V 151936 -> 152064 padded) the
# parked block is 4.6 MiB.
PARK_VMEM_LIMIT = 8 << 20


def _pad(x, rows, cols, fill):
    B, V = x.shape
    return jnp.pad(x, ((0, rows - B), (0, cols - V)), constant_values=fill)


@partial(jax.jit,
         static_argnames=("lp_k", "with_lanes", "park_vmem", "interpret"))
def fused_sample(logits, gumbel, k, p, min_p, raw=None, *, lp_k: int = 0,
                 with_lanes: bool = False, park_vmem=None,
                 interpret: bool = False):
    """Single-pass sample for a (B, V) batch of processed logits.

    Pads V up to a TILE multiple with the NEG sentinel (padded tokens
    carry zero probability mass and can never win either argmax) and B
    up to a ROWS multiple with NEG rows (dropped from the outputs).
    ``park_vmem`` (default: auto — on whenever a row group fits
    ``PARK_VMEM_LIMIT``) parks the logits rows in VMEM across the
    kernel's phases so HBM reads them once instead of once per phase.
    Returns a dict with ``sampled``/``greedy`` (B,) i32, ``tau``/``m``/
    ``l`` (B,) f32, plus — when ``with_lanes`` — the raw-logit softmax
    stats ``m_raw``/``l_raw`` and, for ``lp_k > 0``, the ``top_vals``/
    ``top_idx`` lanes ((B, lp_k), raw-logit values with lax.top_k
    tie-breaking; log-softmax = top_vals - m_raw - log(l_raw)).
    """
    B, V = logits.shape
    bp = -(-B // ROWS) * ROWS
    vp = -(-V // TILE) * TILE
    if park_vmem is None:
        park_vmem = ROWS * vp * 4 <= PARK_VMEM_LIMIT
    params = jnp.zeros((B, LANES), jnp.float32)
    params = params.at[:, 0].set(k.astype(jnp.float32))
    params = params.at[:, 1].set(p.astype(jnp.float32))
    params = params.at[:, 2].set(min_p.astype(jnp.float32))
    args = (_pad(logits.astype(jnp.float32), bp, vp, NEG),
            _pad(gumbel.astype(jnp.float32), bp, vp, 0.0),
            _pad(params, bp, LANES, 0.0))
    if with_lanes:
        args += (_pad(raw.astype(jnp.float32), bp, vp, NEG),)
    outs = fused_sampling_tpu(*args, lp_k=lp_k, with_lanes=with_lanes,
                              park_vmem=bool(park_vmem),
                              interpret=interpret)
    stats = outs[0][:B]
    names = OUT_COLS if with_lanes else OUT_COLS[:5]
    res = {nm: stats[:, i] for i, nm in enumerate(names)}
    res["sampled"] = res["sampled"].astype(jnp.int32)
    res["greedy"] = res["greedy"].astype(jnp.int32)
    if with_lanes and lp_k > 0:
        res["top_vals"] = outs[1][:B, :lp_k]
        res["top_idx"] = outs[2][:B, :lp_k]
    return res
