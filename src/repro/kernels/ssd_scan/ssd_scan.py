"""Pallas TPU kernel for the Mamba-2 SSD inter-chunk state recurrence.

The chunked SSD algorithm (models/ssm.py) reduces the sequential work to
    H_c = decay_c * H_{c-1} + S_c
over nc chunks with per-head state (N, P).  This kernel runs one (batch,
head) cell per grid step, keeping the running state in VMEM while looping
chunks with fori_loop, and emits the *previous* state per chunk (what the
intra-chunk term consumes) plus the final state (the decode handoff).

Whole-chunk-axis blocks: nc*N*P fp32 ≈ 2 MiB at production sizes — fits
VMEM comfortably.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(states_ref, decay_ref, prev_ref, final_ref):
    nc = states_ref.shape[2]          # block = (1, 1, nc, N, P)

    def body(c, h):
        prev_ref[0, 0, c] = h.astype(prev_ref.dtype)
        return h * decay_ref[0, 0, c] + states_ref[0, 0, c].astype(jnp.float32)

    h0 = jnp.zeros(states_ref.shape[3:], jnp.float32)
    h = jax.lax.fori_loop(0, nc, body, h0)
    final_ref[0, 0] = h.astype(final_ref.dtype)


def ssd_state_scan_tpu(states, decay, *, interpret=False):
    """states (B, H, nc, N, P); decay (B, H, nc) ->
    (prev_states (B, H, nc, N, P), final (B, H, N, P))."""
    B, H, nc, N, P = states.shape
    prev, final = pl.pallas_call(
        _kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, nc, N, P), lambda b, h: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, nc), lambda b, h: (b, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, nc, N, P), lambda b, h: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(states, decay)
    return prev, final
