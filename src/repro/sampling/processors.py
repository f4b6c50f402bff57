"""Pure jittable logit processors — single-pass joint-threshold pipeline.

The PR 2 pipeline ran three INDEPENDENT full-vocab sorts and three
softmaxes per slot per step (one inside each of top-k / top-p / min-p).
At V≈128k that is O(V log V) sorted (B, V) temporaries per step — pure
HBM-bandwidth loss in the regime where decode should be memory bound
("Mind the Memory Gap", Recasens et al. 2025).  The key observation is
that every one of the three filters is a *value threshold*:

    top-k   keeps  x >= tau_k     (tau_k = k-th largest logit)
    top-p   keeps  x >= tau_p     (tau_p = smallest logit of the nucleus
                                   of the top-k-filtered distribution)
    min-p   keeps  x >= tau_m     (tau_m = max + log(min_p): the
                                   renormalisation after earlier filters
                                   cancels on both sides of the compare)

and because each filter keeps a top-segment of the value order, their
sequential composition is exactly ``x >= max(tau_k, tau_p, tau_m)``.  So
the whole pipeline needs ONE sort (to read tau_k and the sorted row
top-p integrates over) and ONE softmax (top-p's nucleus mass) — computed
by :func:`joint_threshold` — instead of a sort+softmax per filter.

Three statically-selected tiers share the same threshold semantics
(``SampleFlags.kc`` in sample.py picks one per megastep):

* ``kc == 0``  — full shared sort (any row may need the whole
  distribution, i.e. top-p enabled with top-k disabled);
* ``kc > 0``   — ``lax.top_k(x, kc)`` partial sort: when every row that
  enables top-p also enables top-k (k <= kc), the nucleus is contained
  in the top-kc lanes, so the O(V log V) sort drops to O(V log kc) and
  stays flat as V grows to 128k;
* ``kc == -1`` — no sort at all (only temperature / min-p / penalties
  active anywhere: tau_m needs just the row max).

On TPU the Pallas kernel in ``repro.kernels.fused_sampling`` runs the
full-sort tier (and lane caps above ``sample.KC_MAX``): it derives the
same joint threshold with a tiled histogram refinement and no
materialised sorted copies at all.  The sortless and lane tiers are
faster in XLA there too (``sample.flags_for``).

Every processor remains an EXACT identity at its parameter's disabled
value: dividing by a 1.0 penalty and scaling by a 1.0 temperature are
exact float ops, and the joint threshold degrades to -inf when all three
filters are disabled, so ``jnp.where(x >= -inf, x, _)`` returns ``x``
bit-for-bit.  That exactness is what lets ``SamplingParams()`` reproduce
PR 1's argmax megastep (tests/test_sampling.py::test_greedy_parity).

The per-filter reference processors (`apply_top_k` / `apply_top_p` /
`apply_min_p`) are kept as the executable specification of each filter's
semantics — tests assert the joint threshold matches their composition.

All processors are batched across device slots with ``jax.vmap`` in
sample.py — never loop over slots on the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30      # matches the attention-mask convention in models/


def apply_penalties(logits, counts_full, counts_gen, rep, pres, freq):
    """Repetition / presence / frequency penalties for one slot.

    logits (V,) f32; counts_full (V,) int32 occurrence counts over
    prompt+generated; counts_gen (V,) int32 over generated tokens only;
    rep/pres/freq scalars.  Repetition follows the HF full-context
    convention (divides positive logits, multiplies negative ones, for
    any token seen in prompt OR output); presence/frequency follow the
    OpenAI/vLLM convention and penalize only tokens the model itself
    generated (a prompt that repeats a token must not pre-ban it).
    """
    seen = counts_full > 0
    rep_l = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen, rep_l, logits)
    cg = counts_gen.astype(jnp.float32)
    return logits - freq * cg - pres * (counts_gen > 0).astype(jnp.float32)


def apply_temperature(logits, temperature):
    """Scale by 1/T; T <= 0 (greedy) leaves logits untouched — the
    sampler takes the argmax branch in that case."""
    scale = jnp.where(temperature > 0.0, temperature, 1.0)
    return logits / scale


# ---------------------------------------------------------------------------
# reference per-filter processors (executable spec; not on the hot path)
# ---------------------------------------------------------------------------


def apply_top_k(logits, k):
    """Keep the k highest logits (k == 0 disables).  Ties at the k-th
    value are all kept (standard behavior)."""
    V = logits.shape[-1]
    kth_idx = jnp.clip(k - 1, 0, V - 1)
    kth = jnp.sort(logits)[::-1][kth_idx]
    keep = (logits >= kth) | (k <= 0)
    return jnp.where(keep, logits, _NEG_INF)


def apply_top_p(logits, p):
    """Nucleus sampling: keep the smallest prefix of the sorted
    distribution whose cumulative probability reaches p (p >= 1 disables).
    The top token is always kept (exclusive-cumsum comparison)."""
    sl = jnp.sort(logits)[::-1]
    probs = jax.nn.softmax(sl)
    cum_excl = jnp.cumsum(probs) - probs
    kept = jnp.where(cum_excl < p, sl, jnp.inf)
    kth = jnp.min(kept)
    keep = (logits >= kth) | (p >= 1.0)
    return jnp.where(keep, logits, _NEG_INF)


def apply_min_p(logits, min_p):
    """Drop tokens whose probability is below min_p * max probability
    (min_p == 0 disables)."""
    probs = jax.nn.softmax(logits)
    keep = (probs >= min_p * jnp.max(probs)) | (min_p <= 0.0)
    return jnp.where(keep, logits, _NEG_INF)


# ---------------------------------------------------------------------------
# single-pass joint threshold (the hot path)
# ---------------------------------------------------------------------------


def joint_threshold(logits, k, p, min_p, kc: int = 0):
    """The single value ``tau`` such that the top-k -> top-p -> min-p
    composition keeps exactly ``{x : x >= tau}``.  -inf when all three
    filters are disabled (k <= 0, p >= 1, min_p <= 0).

    Shape-generic over leading batch dims: logits (..., V) with k/p/min_p
    (...,) — the hot path calls it BATCHED on (B, V) rather than under
    ``jax.vmap`` (vmapping the sorted-row reductions lowers to gathers an
    order of magnitude slower than the native batched ops on CPU).

    ``kc`` is the static tier described in the module docstring: 0 =
    full sort, > 0 = ``lax.top_k(x, kc)`` partial sort (valid when every
    DRAWING row has 0 < top_k <= kc — a filterless temperature-only row
    needs the whole distribution and forces the full tier, see
    ``sample.flags_for``), -1 = sortless (valid when no row enables
    top-k or top-p).  Tiers agree on the kept SET for distinct logit
    values; nucleus boundaries may differ by float-reduction order, and
    the partial tier truncates k-th-value TIES that extend past the kc
    lanes (apply_top_k keeps all ties) — which is why the tier is fixed
    per megastep (sample.py).
    """
    k, p, min_p = (jnp.asarray(v) for v in (k, p, min_p))
    if kc < 0:
        return jnp.where(min_p > 0.0,
                         jnp.max(logits, axis=-1) + jnp.log(min_p),
                         -jnp.inf)
    if kc == 0:
        sl = jnp.flip(jnp.sort(logits, axis=-1), -1)  # the ONE sort
    else:
        sl = jax.lax.top_k(logits, kc)[0]             # partial sort
    return tau_from_sorted_rows(sl, k, p, min_p)


def tau_from_sorted_rows(sl, k, p, min_p):
    """Joint threshold from descending(-prefix) rows ``sl`` (..., cap) —
    the full sorted row (cap == V) or the top-kc lanes.  Shared by
    `joint_threshold` and the lane-tier sampler so the nucleus-edge
    semantics live in exactly one place."""
    cap = sl.shape[-1]
    idx = jnp.clip(k - 1, 0, cap - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(sl, idx[..., None], axis=-1)[..., 0]
    tau_k = jnp.where(k > 0, kth, -jnp.inf)
    slk = jnp.where(sl >= tau_k[..., None], sl, _NEG_INF)
    probs = jax.nn.softmax(slk, axis=-1)              # the ONE softmax
    cum_excl = jnp.cumsum(probs, axis=-1) - probs
    kept = jnp.where(cum_excl < p[..., None], slk, jnp.inf)
    tau_p = jnp.where(p < 1.0, jnp.min(kept, axis=-1), -jnp.inf)
    tau_m = jnp.where(min_p > 0.0, sl[..., 0] + jnp.log(min_p), -jnp.inf)
    return jnp.maximum(jnp.maximum(tau_k, tau_p), tau_m)


def joint_filter(logits, k, p, min_p, kc: int = 0):
    """Mask everything below the joint threshold to ``_NEG_INF``."""
    tau = joint_threshold(logits, k, p, min_p, kc)
    return jnp.where(logits >= tau[..., None], logits, _NEG_INF)


def process_logits(logits, counts_full, counts_gen, sp_row, *,
                   pen: bool = True, kc: int = 0):
    """Full pipeline for one slot: penalties -> temperature -> joint
    top-k/top-p/min-p threshold filter.  ``sp_row`` is one row of the
    pack_params arrays.  ``pen=False`` (static) skips the penalty ops
    entirely — the engine sets it when no active slot enables any
    penalty, dropping the per-step (B, V) count reads from the megastep.
    """
    logits = logits.astype(jnp.float32)
    if pen:
        logits = apply_penalties(logits, counts_full, counts_gen,
                                 sp_row["repetition_penalty"],
                                 sp_row["presence_penalty"],
                                 sp_row["frequency_penalty"])
    logits = apply_temperature(logits, sp_row["temperature"])
    return joint_filter(logits, sp_row["top_k"], sp_row["top_p"],
                        sp_row["min_p"], kc)
