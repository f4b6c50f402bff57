"""Pallas TPU fused-sampling kernel: joint top-k/top-p/min-p + Gumbel-max.

Replaces the sampling hot path's sorted (B, V) temporaries with tiled
streaming passes over the vocab.  Grid = (B/8, 7 phases, V/TILE tiles):
each grid step holds one 8-row sublane group of the batch — every block
is (8, TILE) or (8, 128), the TPU's f32 tile — the group axis is
parallel, phases and tiles are sequential so all per-row state lives in
VMEM scratch (the paged_attention pattern):

  phase 0  online-softmax stats: running max m, denominator l, greedy
           argmax — plus, when logprob lanes are requested, the raw-logit
           stats and a streaming top-K merge (the PR 3 transfer plane's
           pre-filter lanes, fused into the same kernel launch).
  phase 1  coarse NB-bucket histogram (counts + exp-mass) of
           ``(m - SPAN, m]``; pick the bucket where the cumulative count
           crosses k; the mass histogram is kept for tau_p's level 0.
  phase 2/3  two count-crossing refinements -> tau_k and Z_kept (the
           kept set's softmax mass) at SPAN/NB^3 ~ 2e-6 nat resolution.
  phase 4/5  two mass-crossing refinements against p * Z_kept -> tau_p
           (level 0 reused phase 1's histogram: no extra pass).
  phase 6  Gumbel-max over the kept set ``x >= max(tau_k, tau_p, tau_m)``.

Layout.  Per-row scalars are (8, 1) columns (row r on sublane r), kept
in an ``(_NST, 8, 128)`` scratch with each value broadcast over lanes.
Histograms are ``(NB, 128)`` with row r's buckets in lane r, so the
phase-end bucket walks (prefix sum by sublane rolls, first crossing by a
sublane min) run for all 8 rows at once; ``_lanes``/``_cols`` move
values between the two forms with a masked reduction.  The per-row
sampling parameters arrive as one (B, 128) f32 block ([k, p, min_p]) and
every per-row result leaves in one (B, 128) f32 block (``OUT_COLS``;
token ids are exact in f32 below 2^24), so no block is narrower than the
(8, 128) tile.

The Gumbel noise is an INPUT (the token-addressed
``sampling.sample.token_gumbel`` rows — one threefry hash of
``fold_in(step_key, token_id)`` per token), not kernel-generated: the
draw stays bitwise-shared with every XLA fallback tier, the
per-sequence stream stays a pure function of (seed, t, token), and the
kernel stays deterministic — which is what lets
tests/test_fused_sampling.py hold it exactly to ``ref.py``.

Histogram binning is scatter-free (bucket-index compare against a
broadcasted iota, then a lane reduction): O(TILE * NB) VPU work per
tile and row, but only O(V) HBM traffic per phase — the trade "Mind the
Memory Gap" calls for in the bandwidth-bound decode regime.

VMEM row parking (``park_vmem=True``, the default whenever the group's
rows fit — see ``ops.PARK_VMEM_LIMIT``): phase 0 copies each logits tile
into an (8, V) VMEM scratch and phases 0-6 read from the scratch,
collapsing the seven HBM reads of the logits to ONE.  The phase-idle
inputs stop streaming too: each input's BlockSpec index map pins its
block while the phase doesn't consume it (logits after phase 0, the raw
rows after phase 0, the Gumbel rows before phase 6), so the pipeline
fetches every operand from HBM exactly once.  The math is identical to
the unparked kernel — both are held to ``ref.py`` by the interpret-mode
parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_sampling.ref import LEVELS, NB, NEG, SPAN

assert LEVELS == 3, "kernel phase layout is built for 3 histogram levels"

TILE = 512
ROWS = 8                    # batch rows per grid step (one f32 sublane group)
LANES = 128
OUT_COLS = ("sampled", "greedy", "tau", "m", "l", "m_raw", "l_raw")
_BIG = 3.0e38               # "no position" for first-occurrence argmax

# per-row state slots (one (ROWS, LANES) plane each)
_M, _L, _HI, _W, _REM, _ABOVE, _INB, _TAU_K, _Z, _TARGET, _ABOVE_P, \
    _TAU_P, _TAU, _GVAL, _GIDX, _SVAL, _SIDX, _M_RAW, _L_RAW = range(19)
_NST = 19

_PH_STATS, _PH_COARSE, _PH_K1, _PH_K2, _PH_P1, _PH_P2, _PH_SAMPLE = range(7)
_NPH = 7


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _first_max(x, mx, pos):
    """Position of the first ``x == mx`` per row (jnp.argmax tie rule)."""
    return jnp.min(jnp.where(x == mx, pos, _BIG), axis=1, keepdims=True)


def _kernel(prm_ref, x_ref, g_ref, *rest, tiles: int, lanes_k: int,
            park: bool):
    rest = list(rest)
    raw_ref = rest.pop(0) if lanes_k >= 0 else None
    o_stats = rest.pop(0)
    if lanes_k > 0:
        o_tv, o_ti = rest.pop(0), rest.pop(0)
    st, hist_cnt, hist_mass, coarse_mass = rest[:4]
    rest = rest[4:]
    if lanes_k > 0:
        topv, topi = rest[:2]
        rest = rest[2:]
    xv = rest[0] if park else None

    f32 = jnp.float32
    ph = pl.program_id(1)
    j = pl.program_id(2)
    last = j == tiles - 1
    if park:
        # phase 0 parks each tile in the VMEM rows; every phase reads the
        # scratch (x_ref is pinned to block 0 after phase 0)
        off = pl.multiple_of(j * TILE, TILE)

        @pl.when(ph == _PH_STATS)
        def _park_tile():
            xv[:, pl.ds(off, TILE)] = x_ref[...]
        x = xv[:, pl.ds(off, TILE)]
    else:
        x = x_ref[...]                                     # (ROWS, TILE)
    pos = (j * TILE + jax.lax.broadcasted_iota(
        jnp.int32, (ROWS, TILE), 1)).astype(f32)
    eye = _iota((ROWS, LANES), 0) == _iota((ROWS, LANES), 1)
    sub = _iota((NB, LANES), 0)                            # bucket index

    def get(c):                              # (ROWS, 1) column
        return st[c][:, :1]

    def put(c, v):
        st[c] = jnp.broadcast_to(v, (ROWS, LANES))

    def _lanes(col):                         # (ROWS, 1) -> (1, LANES)
        return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)

    def _cols(v):                            # (1, LANES) -> (ROWS, 1)
        return jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True)

    def lane(c):
        return _lanes(get(c))

    def put_lane(c, v):
        put(c, _cols(v))

    def prm(i):                              # [k, p, min_p] columns
        return prm_ref[:, i:i + 1]

    @pl.when((ph == _PH_STATS) & (j == 0))
    def _init():
        put(_M, jnp.full((ROWS, 1), -jnp.inf, f32))
        put(_L, jnp.zeros((ROWS, 1), f32))
        put(_GVAL, jnp.full((ROWS, 1), -jnp.inf, f32))
        put(_GIDX, jnp.zeros((ROWS, 1), f32))
        if lanes_k >= 0:
            put(_M_RAW, jnp.full((ROWS, 1), -jnp.inf, f32))
            put(_L_RAW, jnp.zeros((ROWS, 1), f32))
            if lanes_k > 0:
                topv[...] = jnp.full(topv.shape, NEG, f32)
                topi[...] = jnp.zeros(topi.shape, f32)

    # ---------------------------------------------------- phase 0: stats
    def _merge_topk(r):
        """Streaming top-K merge of this tile into the kept lanes.  Ties
        break to the lowest index, as lax.top_k: kept lanes (earlier
        tiles) win ties against the tile, first occurrence within each."""
        klane = _iota(topv.shape, 1)
        cp, ip, ct = topv[...], topi[...], r
        nv = jnp.full(topv.shape, NEG, f32)
        ni = jnp.zeros(topi.shape, f32)
        for kk in range(lanes_k):
            mp = jnp.max(cp, axis=1, keepdims=True)
            mt = jnp.max(ct, axis=1, keepdims=True)
            take_p = mp >= mt
            ap = _first_max(cp, mp, klane)
            it = _first_max(ct, mt, pos)
            ip_sel = jnp.sum(jnp.where(klane == ap, ip, 0.0), axis=1,
                             keepdims=True)
            nv = jnp.where(klane == kk, jnp.where(take_p, mp, mt), nv)
            ni = jnp.where(klane == kk, jnp.where(take_p, ip_sel, it), ni)
            cp = jnp.where(take_p & (klane == ap), NEG, cp)
            ct = jnp.where(jnp.logical_not(take_p) & (pos == it), NEG, ct)
        topv[...] = nv
        topi[...] = ni

    @pl.when(ph == _PH_STATS)
    def _stats():
        m_prev = get(_M)
        tmax = jnp.max(x, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, tmax)
        put(_L, get(_L) * jnp.exp(m_prev - m_new)
            + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True))
        put(_M, m_new)
        better = tmax > get(_GVAL)
        put(_GIDX, jnp.where(better, _first_max(x, tmax, pos), get(_GIDX)))
        put(_GVAL, jnp.where(better, tmax, get(_GVAL)))

        if lanes_k >= 0:
            r = raw_ref[...]
            mr_prev = get(_M_RAW)
            mr_new = jnp.maximum(mr_prev, jnp.max(r, axis=1, keepdims=True))
            put(_L_RAW, get(_L_RAW) * jnp.exp(mr_prev - mr_new)
                + jnp.sum(jnp.exp(r - mr_new), axis=1, keepdims=True))
            put(_M_RAW, mr_new)
            if lanes_k > 0:
                _merge_topk(r)

    # ------------------------------------------- histogram accumulation
    def _bin(sel):
        hi, width = get(_HI), get(_W)
        sel = sel & (x <= hi)
        idx = jnp.clip(jnp.floor((hi - x) / width), 0, NB - 1)
        w = jnp.exp(x - get(_M))
        bucket = _iota((NB, TILE), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (NB, LANES), 1)
        cnt = jnp.zeros((NB, LANES), f32)
        mass = jnp.zeros((NB, LANES), f32)
        for r in range(ROWS):
            oh = (bucket == idx[r:r + 1]) & sel[r:r + 1]        # (NB, TILE)
            c = jnp.sum(jnp.where(oh, 1.0, 0.0), axis=1, keepdims=True)
            s = jnp.sum(jnp.where(oh, w[r:r + 1], 0.0), axis=1,
                        keepdims=True)
            cnt = jnp.where(row == r, c, cnt)
            mass = jnp.where(row == r, s, mass)
        hist_cnt[...] += cnt
        hist_mass[...] += mass

    def _zero_hist():
        hist_cnt[...] = jnp.zeros(hist_cnt.shape, f32)
        hist_mass[...] = jnp.zeros(hist_mass.shape, f32)

    def _prefix(h):
        """Inclusive prefix sum over buckets (sublanes), per lane."""
        s = 1
        while s < NB:
            h = h + jnp.where(sub >= s, pltpu.roll(h, s, 0), 0.0)
            s *= 2
        return h

    def _at(a, b):
        return jnp.sum(jnp.where(sub == b, a, 0.0), axis=0, keepdims=True)

    def _crossing(per, target):
        """First bucket whose cumulative weight reaches ``target`` (the
        bottom bucket when none does) and the weight strictly above it."""
        cum = _prefix(per)
        b = jnp.min(jnp.where(cum >= target, sub, NB - 1.0), axis=0,
                    keepdims=True)
        return b, _at(cum, b) - _at(per, b)

    @pl.when((ph == _PH_STATS) & last)
    def _open_coarse():
        put(_HI, get(_M))
        put(_W, jnp.full((ROWS, 1), SPAN / NB, f32))
        put(_REM, jnp.clip(prm(0), 1, tiles * TILE))
        put(_ABOVE, jnp.zeros((ROWS, 1), f32))
        _zero_hist()

    @pl.when((ph == _PH_COARSE) | (ph == _PH_K1) | (ph == _PH_K2))
    def _bin_k():
        _bin(jnp.ones(x.shape, bool))

    def _k_level_update():
        cnt, mass = hist_cnt[...], hist_mass[...]
        bk, above_cnt = _crossing(cnt, lane(_REM))
        put_lane(_ABOVE, lane(_ABOVE) + _at(_prefix(mass), bk)
                 - _at(mass, bk))
        put_lane(_REM, lane(_REM) - above_cnt)
        put_lane(_INB, _at(mass, bk))
        width = lane(_W)
        hi = lane(_HI) - bk * width
        put_lane(_HI, hi)
        put_lane(_TAU_K, hi - width)
        put_lane(_W, width / NB)
        _zero_hist()

    @pl.when((ph == _PH_COARSE) & last)
    def _end_coarse():
        coarse_mass[...] = hist_mass[...]
        _k_level_update()

    @pl.when(((ph == _PH_K1) | (ph == _PH_K2)) & last)
    def _end_k():
        _k_level_update()

        @pl.when(ph == _PH_K2)
        def _open_p():
            kk = _lanes(prm(0))
            put_lane(_TAU_K, jnp.where(kk > 0, lane(_TAU_K), -jnp.inf))
            z = jnp.where(kk > 0, lane(_ABOVE) + lane(_INB), lane(_L))
            target = _lanes(prm(1)) * z
            put_lane(_TARGET, target)
            bp, above = _crossing(coarse_mass[...], target)
            put_lane(_ABOVE_P, above)
            w0 = SPAN / NB
            hi = lane(_M) - bp * w0
            put_lane(_HI, hi)
            put_lane(_TAU_P, hi - w0)
            put(_W, jnp.full((ROWS, 1), w0 / NB, f32))

    @pl.when((ph == _PH_P1) | (ph == _PH_P2))
    def _bin_p():
        _bin(x >= get(_TAU_K))

    @pl.when(((ph == _PH_P1) | (ph == _PH_P2)) & last)
    def _end_p():
        bp, above_l = _crossing(hist_mass[...],
                                lane(_TARGET) - lane(_ABOVE_P))
        put_lane(_ABOVE_P, lane(_ABOVE_P) + above_l)
        width = lane(_W)
        hi = lane(_HI) - bp * width
        put_lane(_HI, hi)
        put_lane(_TAU_P, hi - width)
        put_lane(_W, width / NB)
        _zero_hist()

        @pl.when(ph == _PH_P2)
        def _close_tau():
            tau_p = jnp.where(prm(1) < 1.0, get(_TAU_P), -jnp.inf)
            min_p = prm(2)
            tau_m = jnp.where(min_p > 0.0, get(_M) + jnp.log(min_p),
                              -jnp.inf)
            put(_TAU, jnp.maximum(jnp.maximum(get(_TAU_K), tau_p), tau_m))
            put(_SVAL, jnp.full((ROWS, 1), -jnp.inf, f32))
            put(_SIDX, jnp.zeros((ROWS, 1), f32))

    # ------------------------------------------- phase 6: Gumbel-max draw
    @pl.when(ph == _PH_SAMPLE)
    def _draw():
        s = jnp.where(x >= get(_TAU), x + g_ref[...], NEG)
        tmax = jnp.max(s, axis=1, keepdims=True)
        better = tmax > get(_SVAL)
        put(_SIDX, jnp.where(better, _first_max(s, tmax, pos), get(_SIDX)))
        put(_SVAL, jnp.where(better, tmax, get(_SVAL)))

    @pl.when((ph == _PH_SAMPLE) & last)
    def _flush():
        cols = [_SIDX, _GIDX, _TAU, _M, _L]
        if lanes_k >= 0:
            cols += [_M_RAW, _L_RAW]
        col = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1)
        out = jnp.zeros((ROWS, LANES), f32)
        for i, c in enumerate(cols):
            out = jnp.where(col == i, get(c), out)
        o_stats[...] = out
        if lanes_k > 0:
            o_tv[...] = topv[...]
            o_ti[...] = topi[...].astype(jnp.int32)


def fused_sampling_tpu(logits, gumbel, params, raw=None, *, lp_k: int = 0,
                       with_lanes: bool = False, park_vmem: bool = False,
                       interpret: bool = False):
    """logits/gumbel (B, V) f32 with B a multiple of ROWS and V a multiple
    of TILE (pad with the NEG sentinel / zeros — see ops.fused_sample);
    params (B, LANES) f32 with columns [k, p, min_p]; raw (B, V) only
    when ``with_lanes``.  ``park_vmem`` parks each row group's logits in
    an (ROWS, V) VMEM scratch across the phases (caller checks it fits).

    Returns (stats[, top_vals, top_idx]): stats (B, LANES) f32 with the
    ``OUT_COLS`` columns; the top-K lanes (B, KP) with KP = lp_k rounded
    up to LANES (columns past lp_k are padding)."""
    B, V = logits.shape
    assert B % ROWS == 0 and V % TILE == 0, (B, V)
    tiles = V // TILE
    lanes_k = (max(lp_k, 0) if with_lanes else -1)
    kp = -(-max(lp_k, 1) // LANES) * LANES

    row = pl.BlockSpec((ROWS, TILE), lambda g, ph, jj: (g, jj))
    per_row = pl.BlockSpec((ROWS, LANES), lambda g, ph, jj: (g, 0))
    lane = pl.BlockSpec((ROWS, kp), lambda g, ph, jj: (g, 0))

    def _phase_pinned(active_ph):
        """Stream the rows' tiles only while ``active_ph`` consumes them;
        every other phase pins the block index so the pipeline does not
        re-fetch the operand from HBM."""
        return pl.BlockSpec(
            (ROWS, TILE),
            lambda g, ph, jj: (g, jnp.where(ph == active_ph, jj, 0)))

    if park_vmem:
        in_specs = [_phase_pinned(_PH_STATS), _phase_pinned(_PH_SAMPLE)] \
            + ([_phase_pinned(_PH_STATS)] if with_lanes else [])
    else:
        in_specs = [row, row] + ([row] if with_lanes else [])
    out_shapes = [jax.ShapeDtypeStruct((B, LANES), jnp.float32)]
    out_specs = [per_row]
    if lanes_k > 0:
        out_shapes += [jax.ShapeDtypeStruct((B, kp), jnp.float32),
                       jax.ShapeDtypeStruct((B, kp), jnp.int32)]
        out_specs += [lane, lane]

    scratch = [pltpu.VMEM((_NST, ROWS, LANES), jnp.float32),
               pltpu.VMEM((NB, LANES), jnp.float32),     # hist counts
               pltpu.VMEM((NB, LANES), jnp.float32),     # hist mass
               pltpu.VMEM((NB, LANES), jnp.float32)]     # coarse mass
    if lanes_k > 0:
        scratch += [pltpu.VMEM((ROWS, kp), jnp.float32),
                    pltpu.VMEM((ROWS, kp), jnp.float32)]
    if park_vmem:
        scratch += [pltpu.VMEM((ROWS, V), jnp.float32)]   # parked logits

    kernel = functools.partial(_kernel, tiles=tiles, lanes_k=lanes_k,
                               park=park_vmem)
    args = (params, logits, gumbel) + ((raw,) if with_lanes else ())
    return pl.pallas_call(
        kernel,
        grid=(B // ROWS, _NPH, tiles),
        in_specs=[per_row] + in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)
