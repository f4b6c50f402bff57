"""Pallas TPU paged-attention (decode) kernel.

One new token per sequence attends to its KV history stored in a shared
page pool (the two-page lazy allocation layout of §5.2).  The page table is
a scalar-prefetch operand: the BlockSpec index_map reads ``table[b, j]`` to
stream exactly that sequence's pages from HBM — no gather materialization.
Grid = (B, max_pages) with the page axis sequential so the online-softmax
state lives in VMEM scratch.

Pages are (page_size, Hkv, dh) tiles; page_size is chosen as a multiple of
the 128-lane register width by the memory planner.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(table_ref, len_ref, q_ref, kpool_ref, vpool_ref, o_ref,
            m_scr, l_scr, acc_scr, *, page_size: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    npages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    page_start = j * page_size

    @pl.when(page_start < length)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale       # (H, dh)
        k = kpool_ref[0].astype(jnp.float32)           # (page, Hkv, dh)
        v = vpool_ref[0].astype(jnp.float32)
        H, dh = q.shape
        page, Hkv, _ = k.shape
        G = H // Hkv
        qg = q.reshape(Hkv, G, dh)
        s = jnp.einsum("hgd,phd->hgp", qg, k,
                       preferred_element_type=jnp.float32)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG)
        m_prev = m_scr[...]                            # (H, 1)
        sm = s.reshape(H, page)
        m_new = jnp.maximum(m_prev, jnp.max(sm, axis=1, keepdims=True))
        p = jnp.exp(sm - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jnp.einsum("hgp,phd->hgd", p.reshape(Hkv, G, page), v,
                        preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv.reshape(H, dh)

    @pl.when(j == npages - 1)
    def _flush():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_tpu(q, k_pool, v_pool, page_table, lengths, *,
                        interpret=False):
    """q (B, H, dh); pools (num_pages, page, Hkv, dh);
    page_table (B, max_pages) int32; lengths (B,) int32."""
    B, H, dh = q.shape
    num_pages, page, Hkv, _ = k_pool.shape
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(_kernel, page_size=page, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, j, tab, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, Hkv, dh),
                         lambda b, j, tab, ln: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, Hkv, dh),
                         lambda b, j, tab, ln: (tab[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, j, tab, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, q, k_pool, v_pool)
    return out
