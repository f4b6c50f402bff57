"""Batched sampling entry point + the scan-carried PRNG/penalty state.

Key discipline
--------------
The key for a sequence's t-th generated token is
``fold_in(PRNGKey(seed), t)`` — derived fresh every step from the
constant per-slot base key and a carried token counter, NOT an evolving
split chain.  A split chain would make the stream depend on how many
scan steps the slot sat masked in (batch composition) and would be
unreconstructible after MIGRATE; fold_in(base, t) is a pure function of
(seed, t), so any node can resume the stream from the coroutine's token
count alone.

Sampling itself is the Gumbel-max trick: ``argmax(logits + gumbel)`` is
an exact categorical draw from ``softmax(logits)``, costs one argmax (no
cumsum search), and degrades to plain argmax when temperature <= 0.  The
noise is ADDRESSABLE per token: ``token_gumbel`` hashes
``fold_in(step_key, token_id)``, so the lane tier generates noise for
just its kc lane ids, the full tiers for ``arange(V)``, and the Pallas
kernel consumes the same rows as an input — every path realizes the
bitwise-identical (seed, t, token) -> noise mapping.

Static execution plan (:class:`SampleFlags`)
--------------------------------------------
The logit pipeline has three data-independent degrees of freedom that
are wasteful to decide on device every step, so the engine derives them
ON THE HOST from the active batch's SamplingParams and bakes them into
the megastep executable (they are part of the jit cache key):

* ``kc`` — the sort tier of ``processors.joint_threshold``: 0 full
  sort, > 0 partial ``lax.top_k`` sort, -1 sortless.
* ``backend`` — ``"pallas"`` routes the filter + draw through the fused
  single-pass kernel (``repro.kernels.fused_sampling``), compiled;
  ``"xla"`` runs the ``kc`` tier in plain XLA.  The platform and the
  tier decide together: the kernel only on a TPU (``default_backend``)
  and only for the full-sort tier or a lane cap above ``KC_MAX``, where
  it beats XLA's sort; the sortless and lane tiers are faster in XLA.
* ``pen`` — False drops the penalty ops AND the per-step (B, V) count
  updates from the scan when no active slot enables a penalty.

Every tier (and the kernel) consumes the same TOKEN-indexed noise from
the same fold_in key and computes the same kept set, so the realized
stream is a pure function of (seed, t, logits) no matter which tier the
batch composition selects — the PR 2 reproducibility contract survives
the tiering.  The only residual flags-sensitivity is float-reduction
order in the nucleus-mass sums (kc lanes vs V entries), which can flip
a nucleus-boundary token only when its exclusive cumulative mass lands
within an ulp of ``top_p``.

State carried per slot through the megastep scan:
* ``gen_count``     (B,) int32   — tokens generated so far (key index)
* ``counts``        (B, V) int32 — generated-token counts (presence/
  frequency penalties; advanced in-scan only when ``flags.pen``)
* ``prompt_counts`` (B, V) int32 — prompt-token counts (loop-invariant;
  repetition penalty sees prompt_counts + counts)

All are re-derivable host-side from the coroutine (len(generated),
bincounts of generated / prompt), which is why YIELD/COMBINE/MIGRATE need
no extra device state movement.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.sampling.processors import apply_penalties, apply_temperature


# The largest lane cap at which the lane tier (one ``lax.top_k`` plus
# (B, kc) lane math) beats the fused-sampling kernel on a TPU v5e, at
# (32, 151936) f32 with penalties and top-5 log-prob lanes
# (benchmarks/sampling_tiers.py; PERF.md section 5).
KC_MAX = 4096

SAMPLE_TIERS = ("kernel", "lanes", "sortless", "sort")


@dataclasses.dataclass(frozen=True)
class SampleFlags:
    """Static (host-decided, jit-keyed) execution plan for one megastep:
    the ``kc`` tier, and the kernel or XLA to run it (``flags_for``)."""
    backend: str = "xla"     # "xla" | "pallas"
    pen: bool = True         # any penalty enabled in the active batch
    kc: int = 0              # sort tier: 0 full, >0 top-kc, -1 sortless
    mixed: bool = True       # any greedy (temperature <= 0) row present
    stops: bool = True       # any stop-token set non-empty

    @property
    def tier(self) -> str:
        """The path a page with this plan samples on, one of
        ``SAMPLE_TIERS``: the kernel, or XLA's lane, sortless or
        full-sort tier."""
        if self.backend == "pallas":
            return "kernel"
        return "lanes" if self.kc > 0 else "sortless" if self.kc < 0 \
            else "sort"


DEFAULT_FLAGS = SampleFlags()


def default_backend() -> str:
    """Where the fused-sampling kernel can run: ``"pallas"`` on TPUs,
    ``"xla"`` everywhere else.  ``flags_for`` uses the kernel only for
    the tiers it wins on this platform."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def flags_for(sps, vocab: int) -> SampleFlags:
    """Derive the static plan from the active slots' SamplingParams.

    ``kc`` buckets the top-k cap to a pow2 (floor 8) so slot churn does
    not mint a new executable per distinct k.  The lane tier (kc > 0)
    requires EVERY drawing row (temperature > 0, not greedy-default) to
    have top-k active — a filterless or top-p-only row samples from a
    set no static cap bounds, so any such row forces the full-sort tier;
    greedy rows are fine either way (lane 0 IS the argmax).

    The kernel runs a tier only where it beats XLA: on a TPU, for the
    full-sort tier (kc == 0) or a lane cap above ``KC_MAX``.  The
    sortless and the other lane tiers take XLA's code."""
    act = [s for s in sps if not s.is_greedy_default]
    pen = any(s.repetition_penalty != 1.0 or s.presence_penalty != 0.0
              or s.frequency_penalty != 0.0 for s in act)
    drawing = [s for s in act if s.temperature > 0.0]
    ks = [s.top_k for s in drawing if s.top_k > 0]
    if drawing and all(s.top_k > 0 for s in drawing):
        kc = max(_pow2(max(ks)), 8)
        if kc >= vocab:
            kc = 0
    elif any(s.top_k > 0 or s.top_p < 1.0 for s in drawing):
        kc = 0
    else:
        kc = -1
    kernel = default_backend() == "pallas" and (kc == 0 or kc > KC_MAX)
    return SampleFlags(backend="pallas" if kernel else "xla", pen=pen,
                       kc=kc, mixed=any(s.temperature <= 0.0 for s in sps),
                       stops=any(s.stop for s in sps))


def base_keys_host(seeds) -> np.ndarray:
    """(B,) uint32 seeds -> (B, 2) raw threefry key array, built on the
    host: ``PRNGKey(s)`` for a 32-bit seed is just ``[0, s]`` (verified
    against ``jax.random.PRNGKey`` in tests), so slot installs never pay
    an eager device dispatch for key derivation."""
    seeds = np.asarray(seeds, np.uint32)
    return np.stack([np.zeros_like(seeds), seeds], axis=-1)


def base_keys(seeds) -> jnp.ndarray:
    """(B,) uint32 seeds -> (B, 2) raw threefry key array."""
    return jnp.asarray(base_keys_host(seeds))


def _bincounts(token_lists, vocab: int) -> np.ndarray:
    out = np.zeros((len(token_lists), vocab), np.int32)
    for i, toks in enumerate(token_lists):
        if toks:
            out[i] = np.bincount(
                np.asarray(toks, np.int64), minlength=vocab)[:vocab]
    return out


def init_state(seeds, prompt_lists, generated_lists,
               vocab: int) -> Dict[str, np.ndarray]:
    """Host-side state for a batch of slots (install/prefill time).

    seeds (B,) ints; prompt_lists / generated_lists: per-slot token ids
    (penalty counts and the PRNG position are recomputed from them, never
    migrated as device state)."""
    return {"seed": np.asarray(seeds, np.uint32),
            "gen_count": np.asarray([len(g) for g in generated_lists],
                                    np.int32),
            "counts": _bincounts(generated_lists, vocab),
            "prompt_counts": _bincounts(prompt_lists, vocab)}


def step_keys(base, gen_count):
    """Per-slot key for the current step: fold_in(base_b, gen_count_b)."""
    return jax.vmap(jax.random.fold_in)(base, gen_count)


def token_gumbel(keys, ids):
    """ADDRESSABLE per-token Gumbel noise: g[b, j] is a pure function of
    (keys[b], ids[b, j]) — one threefry hash per addressed token, the
    uniform taken from the first word of ``fold_in(key, token_id)``.

    Token-indexed addressing is what lets every tier realize the
    bitwise-identical stream while generating only the noise it needs:
    the lane tier hashes its kc lane ids (V-independent), the full tiers
    hash ``arange(V)``, and the Pallas kernel consumes the same rows as
    an input.  keys (B, 2) uint32; ids (B, I) int32 -> (B, I) f32."""
    bits = jax.vmap(lambda k, row: jax.vmap(
        lambda v: jax.random.fold_in(k, v))(row))(keys, ids)[..., 0]
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2 ** -24)
    u = u + jnp.float32(2 ** -25)        # (0, 1): log(log) stays finite
    return -jnp.log(-jnp.log(u))


def _gumbel_rows(keys, V: int):
    """(B, V) full-vocab noise rows: ``token_gumbel`` at every id."""
    B = keys.shape[0]
    ids = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32)[None], (B, V))
    return token_gumbel(keys, ids)


def sample_one(logits, counts_full, counts_gen, sp_row, key,
               flags: SampleFlags = DEFAULT_FLAGS):
    """Sample one token for one slot.  logits (V,) f32, counts_* (V,)
    i32, sp_row: one row of pack_params arrays, key: (2,) raw PRNG key.
    Returns int32 token id.

    Reference single-row form.  The hot path (`sample` / `sample_step`)
    is natively batched and tier-selected but consumes the same
    token-indexed Gumbel row, so it realizes the same draw as this
    function (up to nucleus-boundary ulp ties, see `flags_for`)."""
    from repro.sampling.processors import process_logits

    proc = process_logits(logits, counts_full, counts_gen, sp_row,
                          pen=flags.pen, kc=flags.kc)
    greedy_tok = jnp.argmax(proc)
    gumbel = token_gumbel(key[None], jnp.arange(
        proc.shape[-1], dtype=jnp.int32)[None])[0]
    sampled_tok = jnp.argmax(proc + gumbel)
    return jnp.where(sp_row["temperature"] <= 0.0, greedy_tok,
                     sampled_tok).astype(jnp.int32)


def _processed(logits, counts_full, counts_gen, sp, flags: SampleFlags):
    """Batched penalties + temperature (the cheap elementwise prefix the
    kernel does not fold in)."""
    x = logits.astype(jnp.float32)
    if flags.pen:
        x = jax.vmap(apply_penalties)(x, counts_full, counts_gen,
                                      sp["repetition_penalty"],
                                      sp["presence_penalty"],
                                      sp["frequency_penalty"])
    return jax.vmap(apply_temperature)(x, sp["temperature"])


def _xla_lanes(raw, tokens, lp_k: int):
    """Logprob lanes from raw logits — bitwise-identical math to
    models.transformer.pack_logprob_block (log_softmax + lax.top_k)."""
    lp = jax.nn.log_softmax(raw.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(lp, tokens[:, None].astype(jnp.int32),
                                 axis=1)[:, 0]
    lanes = {"chosen_lp": chosen, "top_vals": None, "top_idx": None}
    if lp_k > 0:
        lanes["top_vals"], lanes["top_idx"] = jax.lax.top_k(lp, lp_k)
    return lanes


def _sample_impl(logits, counts_full, counts_gen, sp, keys,
                 flags: SampleFlags, raw=None, lp_k: Optional[int] = None):
    """Shared batched core: returns (tokens (B,) i32, lanes | None).

    ``raw`` (B, V) are the PRE-pipeline model logits the logprob plane
    reports (PR 3 contract: logprobs are pre-filter); lanes are computed
    in the same kernel invocation on the pallas path, or with one
    log_softmax + lax.top_k on the XLA path."""
    if flags.backend == "pallas":
        from repro.kernels.fused_sampling.ops import fused_sample

        proc = _processed(logits, counts_full, counts_gen, sp, flags)
        gumbel = _gumbel_rows(keys, proc.shape[-1])
        out = fused_sample(proc, gumbel, sp["top_k"], sp["top_p"],
                           sp["min_p"], raw=raw,
                           lp_k=0 if lp_k is None else max(lp_k, 0),
                           with_lanes=lp_k is not None)
        tokens = (jnp.where(sp["temperature"] <= 0.0, out["greedy"],
                            out["sampled"]) if flags.mixed
                  else out["sampled"]).astype(jnp.int32)
        lanes = None
        if lp_k is not None:
            logz = out["m_raw"] + jnp.log(out["l_raw"])
            lanes = {"chosen_lp": jnp.take_along_axis(
                         raw.astype(jnp.float32),
                         tokens[:, None], axis=1)[:, 0] - logz,
                     "top_vals": None, "top_idx": None}
            if lp_k > 0:
                lanes["top_vals"] = out["top_vals"] - logz[:, None]
                lanes["top_idx"] = out["top_idx"]
        return tokens, lanes

    # XLA fallback, natively batched (vmapping the sorted-row reductions
    # is an order of magnitude slower on CPU) — same keep-set as
    # sample_one's per-row pipeline
    if flags.kc > 0:
        tokens = _sample_topk_lanes(logits, counts_full, counts_gen, sp,
                                    keys, flags)
    else:
        from repro.sampling.processors import _NEG_INF, joint_threshold
        proc = _processed(logits, counts_full, counts_gen, sp, flags)
        tau = joint_threshold(proc, sp["top_k"], sp["top_p"], sp["min_p"],
                              flags.kc)
        proc = jnp.where(proc >= tau[:, None], proc, _NEG_INF)
        sampled = jnp.argmax(proc + _gumbel_rows(keys, proc.shape[-1]),
                             axis=-1)
        if flags.mixed:
            greedy = jnp.argmax(proc, axis=-1)
            sampled = jnp.where(sp["temperature"] <= 0.0, greedy, sampled)
        tokens = sampled.astype(jnp.int32)
    lanes = _xla_lanes(raw, tokens, lp_k) if lp_k is not None else None
    return tokens, lanes


def _sample_topk_lanes(logits, counts_full, counts_gen, sp, keys,
                       flags: SampleFlags):
    """Top-kc-tier draw in the (B, kc) top-k lanes.

    When every drawing row has top-k active (<= kc), the kept set is
    contained in the top-kc lanes, so after ONE ``lax.top_k`` over the
    (penalized) logits the temperature, the three thresholds and the
    argmax all run on (B, kc) instead of (B, V) — the per-step sort work
    drops from O(V log V) to O(V log kc) (the benchmark sweep's
    large-vocab scaling) and the greedy token is lane 0 for free.
    Gumbel noise stays TOKEN-indexed: it is hashed at the lane token
    ids alone, and equals the full fold_in(seed, t) noise row there, so
    every tier, the Pallas kernel and the looped baseline realize the
    identical stream for the same (seed, t, logits) regardless of which
    tier the batch composition selects."""
    from repro.sampling.processors import _NEG_INF, tau_from_sorted_rows

    x = logits.astype(jnp.float32)
    if flags.pen:
        x = jax.vmap(apply_penalties)(x, counts_full, counts_gen,
                                      sp["repetition_penalty"],
                                      sp["presence_penalty"],
                                      sp["frequency_penalty"])
    # the ONE (B, V) pass.  The barrier keeps it XLA's TopK on a TPU:
    # without it the lane-0 slices below fold into the top-k and the
    # compiler sorts the whole row instead (6-7 ms at 32 x 151936 on a
    # v5e, against 0.5 ms; PERF.md section 5)
    sl, si = jax.lax.optimization_barrier(jax.lax.top_k(x, flags.kc))
    scale = jnp.where(sp["temperature"] > 0.0, sp["temperature"], 1.0)
    sl = sl / scale[:, None]
    tau = tau_from_sorted_rows(sl, sp["top_k"], sp["top_p"], sp["min_p"])
    masked = jnp.where(sl >= tau[:, None], sl, _NEG_INF)
    g = token_gumbel(keys, si)           # noise for the kc lane ids only
    lane = jnp.argmax(masked + g, axis=-1)
    sampled = jnp.take_along_axis(si, lane[:, None], axis=-1)[:, 0]
    if flags.mixed:
        sampled = jnp.where(sp["temperature"] <= 0.0, si[:, 0], sampled)
    return sampled.astype(jnp.int32)


def sample(logits, counts_full, counts_gen, sp, keys,
           flags: SampleFlags = DEFAULT_FLAGS):
    """Batched sampling across device slots.

    logits (B, V), counts_* (B, V), sp: dict of (B,)-rows from
    pack_params (the "stop"/"seed" entries are ignored here), keys (B, 2).
    """
    return _sample_impl(logits, counts_full, counts_gen, sp, keys,
                        flags)[0]


def stop_hit(tokens, stop_table):
    """(B,) bool: did slot b's token land in its stop set?  stop_table
    (B, MAX_STOP_TOKENS) int32 padded with -1 (never matches)."""
    return jnp.any(tokens[:, None] == stop_table, axis=1)


def sample_step(logits, remaining, state, sp,
                flags: SampleFlags = DEFAULT_FLAGS,
                lp_k: Optional[int] = None):
    """One fused-megastep sampling step for the whole batch.

    Consumes the (B, V) logits the model head produced, draws one token
    per slot with the per-slot fold_in key, and advances the carried
    state for LIVE slots only (masked slots must not consume randomness
    or counts, or batch composition would perturb the stream).

    Returns ``(next_tokens (B,) i32, live (B,) bool, new_remaining,
    new_state)`` — plus a lanes dict appended when ``lp_k`` is not None
    (the pre-filter logprob lanes for the transfer plane, computed from
    the RAW logits in the same pass on the kernel path).

    Stop-token hits zero the slot's remaining AFTER the stop token is
    emitted, exactly mirroring the host-side truncation.
    """
    base = state["base_key"]
    gen_count = state["gen_count"]
    counts = state["counts"]
    prompt_counts = state["prompt_counts"]
    keys = step_keys(base, gen_count)
    cf = prompt_counts + counts if flags.pen else counts
    nxt, lanes = _sample_impl(logits, cf, counts, sp, keys, flags,
                              raw=logits if lp_k is not None else None,
                              lp_k=lp_k)
    live = remaining > 0
    if flags.pen:
        B = nxt.shape[0]
        counts = counts.at[jnp.arange(B), nxt].add(live.astype(jnp.int32))
    gen_count = gen_count + live.astype(jnp.int32)
    new_remaining = remaining - live.astype(jnp.int32)
    if flags.stops:
        hit = stop_hit(nxt, sp["stop"]) & live
        new_remaining = jnp.where(hit, 0, new_remaining)
    new_state = {"base_key": base, "gen_count": gen_count, "counts": counts,
                 "prompt_counts": prompt_counts}
    if lp_k is None:
        return nxt, live, new_remaining, new_state
    return nxt, live, new_remaining, new_state, lanes
