"""Pallas TPU flash-attention (prefill) kernel.

Canonical TPU tiling: grid = (batch*kv_heads*q_per_kv, num_q_blocks,
num_kv_blocks) with the kv axis 'arbitrary' (sequential) so the online
softmax state (m, l, acc) persists in VMEM scratch across kv blocks.
Block shapes are MXU-aligned (q_block x head_dim, kv_block x head_dim).
Causal + sliding-window masking via block-local iota; whole kv blocks that
cannot contribute are skipped with @pl.when.

Validated in interpret mode against ref.py (pure-jnp oracle) over
shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, scale: float, block_q: int,
            block_k: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)                  # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v).astype(jnp.float32)

    if causal:
        # skip kv blocks entirely above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_tpu(q, k, v, *, causal=True, window=0, block_q=128,
                        block_k=128, interpret=False):
    """q (B, Sq, H, dh); k/v (B, Skv, Hkv, dh_v); GQA via head folding.

    Returns (B, Sq, H, dv).  Sq/Skv are padded to block multiples
    internally by ops.py — this entry requires aligned shapes.
    """
    B, Sq, H, dh = q.shape
    Skv, Hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    scale = 1.0 / math.sqrt(dh)

    # fold: one grid row per (b, h)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = jnp.repeat(k, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, Skv, dh)
    vf = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3).reshape(B * H, Skv, dv)

    grid = (B * H, Sq // block_q, Skv // block_k)
    kernel = functools.partial(_kernel, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               seq_q=Sq, seq_k=Skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, dv), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, dv).transpose(0, 2, 1, 3)
