"""Pallas TPU grouped expert GEMM (megablocks-style).

Local EP compute after dispatch: tokens sorted by expert, padded per expert
to token-block multiples.  A scalar-prefetched ``block_expert`` map assigns
each 128-token block to its expert, so the weight BlockSpec streams exactly
one expert's tile per block — a dense MXU matmul per (token-block, F-block)
with zero gather/scatter inside the kernel.

This is the compute core the paper's COMBINE primitive feeds: larger
combined batches -> more full token-blocks per expert -> higher MXU
occupancy (Fig. 2b).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(bexp_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot(
        x_ref[...], w_ref[0],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_gemm_tpu(x, w, block_expert, *, block_t=128, block_f=128,
                     interpret=False):
    """x (T, D) tokens sorted/padded by expert; w (E, D, F);
    block_expert (T/block_t,) int32 expert id per token block.
    Returns (T, F)."""
    T, D = x.shape
    E, _, F = w.shape
    block_f = min(block_f, F)
    assert T % block_t == 0 and F % block_f == 0, (T, block_t, F, block_f)
    nT, nF = T // block_t, F // block_f

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nT, nF),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda i, j, be: (i, 0)),
            pl.BlockSpec((1, D, block_f), lambda i, j, be: (be[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_f), lambda i, j, be: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(block_expert, x, w)


def sort_tokens_by_expert(xt, expert_ids, num_experts, *, block_t=128):
    """Host-side dispatch prep: sort token rows by expert, pad each
    expert's group to a block multiple.  Returns
    (x_sorted (Tp, D), block_expert (Tp/block,), inv_perm, valid mask)."""
    T = xt.shape[0]
    order = jnp.argsort(expert_ids, stable=True)
    sorted_ids = expert_ids[order]
    counts = jnp.bincount(expert_ids, length=num_experts)
    padded = ((counts + block_t - 1) // block_t) * block_t
    offs = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                            jnp.cumsum(padded)])[:-1]
    # position of each sorted token within its expert group
    grp_start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                 jnp.cumsum(counts)])[:-1]
    pos_in_grp = jnp.arange(T) - grp_start[sorted_ids]
    dest = offs[sorted_ids] + pos_in_grp          # sorted position -> slot
    slot_of = jnp.zeros((T,), dest.dtype).at[order].set(dest)  # orig -> slot
    Tp = int(((int(T) + block_t - 1) // block_t + num_experts) * block_t)
    x_sorted = jnp.zeros((Tp, xt.shape[1]), xt.dtype).at[dest].set(xt[order])
    valid = jnp.zeros((Tp,), bool).at[dest].set(True)
    # block -> expert map
    blk = jnp.arange(Tp // block_t) * block_t
    cum = jnp.cumsum(padded)
    block_expert = jnp.searchsorted(cum, blk, side="right").astype(jnp.int32)
    block_expert = jnp.minimum(block_expert, num_experts - 1)
    return x_sorted, block_expert, slot_of, order, valid
