"""Algorithm 1: module-granularity forward pass with intra-forward yields.

The paper's module wrapper (Fig. 4b) turns each neural module into a
coroutine step: attention runs per sub-batch of size B_attn, YIELDs its
hidden states, and the runtime COMBINEs all sub-batches into one
B_moe-sized batch before the (sparse) MoE module.  Control returns to the
host scheduler between every jitted module call — on TPU the yield point
*is* the boundary between two compiled programs (DESIGN.md §3).

This path executes real tokens in the mini-engine (module_granularity=True)
and is what benchmarks/expert_batching.py measures (Fig. 2b reproduction).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models import layers, moe as moe_lib, transformer as T
from repro.models.api import MeshAxes, ModelConfig


# LRU cap on (steps, n_sub, sampled, lp_k) page executables — sized so a
# mixed workload (all pow2 chunk sizes x sampled x logprob variants) does
# not thrash steady-state recompiles
_PAGE_JIT_CAP = 16


def _lru_get(cache: "OrderedDict", key, cap: int, make, owner):
    """Fetch-or-build `key` in an OrderedDict LRU bounded to `cap`.

    A newly built executable comes back wrapped for its first call, which
    traces, lowers and compiles it (or loads it from the compilation
    cache): that call runs in an ``engine.node.compile`` span keyed by
    `key` and advances ``owner.jit_builds`` and ``owner.jit_build_s``."""
    fn = cache.get(key)
    if fn is not None:
        cache.move_to_end(key)
        return fn
    fn = cache[key] = make()
    while len(cache) > cap:
        cache.popitem(last=False)
    return partial(_first_call, fn, key, owner)


def _first_call(fn, key, owner, *args, **kwargs):
    t0 = time.perf_counter()
    with TraceAnnotation("engine.node.compile", key=key):
        out = fn(*args, **kwargs)
    owner.jit_builds += 1
    owner.jit_build_s += time.perf_counter() - t0
    return out


def _sub_slices(B: int, n_sub: int) -> List[slice]:
    """Static sub-batch boundaries covering ALL rows; when B % n_sub != 0
    the later groups absorb the remainder (previously the tail rows were
    silently dropped, which broke any non-divisible active batch)."""
    bounds = [g * B // n_sub for g in range(n_sub + 1)]
    return [slice(bounds[g], bounds[g + 1]) for g in range(n_sub)]


@dataclasses.dataclass
class ModuleTrace:
    """Record of one coroutine step (for overhead accounting, Table 2)."""
    module: str
    layer: int
    batch: int
    tokens: int


class ModuleRuntime:
    """Per-model jitted module functions split at the paper's yield points.

    Yield-point option (b) from Fig. 6: attention | MoE as separate
    coroutine units (option (a) fuses them; option (c) per-expert is noted
    as memory-prohibitive by the paper)."""

    def __init__(self, cfg: ModelConfig, axes: MeshAxes, params,
                 owner=None):
        assert cfg.family in ("moe", "dense"), cfg.family
        self.cfg = cfg
        self.axes = axes
        self.params = params
        # page-executable builds are counted on `owner` (the engine)
        self.jit_builds = 0
        self.jit_build_s = 0.0
        self.owner = owner if owner is not None else self
        # pre-split stacked layer params -> list of per-layer trees
        L = cfg.num_layers
        self.layer_params = [jax.tree.map(lambda x, i=i: x[i],
                                          params["layers"])
                             for i in range(L)]
        self.traces: List[ModuleTrace] = []
        self._embed = jax.jit(self._embed_impl)
        self._attn = jax.jit(self._attn_impl, static_argnames=("nsub",))
        self._ffn = jax.jit(self._ffn_impl)
        self._head = jax.jit(self._head_impl)
        self._head_logits = jax.jit(self._head_logits_impl)
        self._page_cache: "OrderedDict[tuple, Any]" = OrderedDict()

    # --- jitted module bodies ------------------------------------------
    def _embed_impl(self, tokens):
        return T._embed_tokens(self.cfg, self.params, tokens[:, None])

    def _attn_impl(self, p, h, k_cache, v_cache, lengths, nsub):
        """Attention for ONE sub-batch (B_attn rows of the slot arrays)."""
        xn = layers.apply_norm(self.cfg, p["ln1"], h)
        a, kc, vc = layers.attention_decode(self.cfg, p["attn"], xn,
                                            k_cache, v_cache, lengths)
        return h + a, kc, vc

    def _ffn_impl(self, p, h):
        xn = layers.apply_norm(self.cfg, p["ln2"], h)
        if self.cfg.is_moe:
            y, _ = moe_lib.moe_fwd(self.cfg, self.axes, p["moe"], xn)
        else:
            y = layers.mlp_fwd(self.cfg, p["mlp"], xn)
        return h + y

    def _head_impl(self, h):
        return jnp.argmax(self._head_logits_impl(h), axis=-1).astype(
            jnp.int32)

    def _head_logits_impl(self, h):
        """Final norm + lm head -> next-token logits (B, V)."""
        h = layers.apply_norm(self.cfg, self.params["final_norm"], h)
        logits = T.logits_fn(self.cfg, self.params, h)
        return logits[:, 0, :]

    # --- Algorithm 1 ------------------------------------------------------
    def forward_decode(self, tokens, cache, lengths, b_attn: int,
                       on_yield: Optional[Callable] = None,
                       want_logits: bool = False):
        """One decode step for the full active batch with B_attn
        sub-batching and COMBINE before each FFN/MoE.

        tokens (B,), cache pytree with leaves (L,B,S,...), lengths (B,).
        Returns (next_tokens, new_cache) — or (logits, new_cache) with
        ``want_logits`` (the looped-baseline logprob path picks the token
        host-side)."""
        cfg = self.cfg
        B = tokens.shape[0]
        n_sub = max(B // max(b_attn, 1), 1)
        h = self._embed(tokens)
        new_k, new_v = [], []
        for l in range(cfg.num_layers):
            p = self.layer_params[l]
            kc_l, vc_l = cache["k"][l], cache["v"][l]
            h_parts, k_parts, v_parts = [], [], []
            for g, sl in enumerate(_sub_slices(B, n_sub)):
                bsz = sl.stop - sl.start
                hg, kg, vg = self._attn(p, h[sl], kc_l[sl], vc_l[sl],
                                        lengths[sl], n_sub)
                self.traces.append(ModuleTrace("attention", l, bsz, bsz))
                h_parts.append(hg)
                k_parts.append(kg)
                v_parts.append(vg)
                if on_yield is not None:
                    on_yield("attention", l, g)     # intra-forward YIELD
            # COMBINE: concatenate yielded hidden states -> B_moe batch
            h = jnp.concatenate(h_parts, axis=0)
            new_k.append(jnp.concatenate(k_parts, axis=0))
            new_v.append(jnp.concatenate(v_parts, axis=0))
            h = self._ffn(p, h)
            self.traces.append(ModuleTrace(
                "moe" if cfg.is_moe else "mlp", l, B, B))
            if on_yield is not None:
                on_yield("ffn", l, 0)
        cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
        if want_logits:
            return self._head_logits(h), cache
        nxt = self._head(h)
        return nxt, cache

    # --- fused decode page (one program per page) ----------------------
    def forward_decode_page(self, tokens, cache, lengths, remaining,
                            b_attn: int, steps: int, sampling=None,
                            lp_k=None, flags=None):
        """Fused Algorithm-1 decode megastep: one jitted ``lax.scan`` over
        ``steps`` module-granularity decode steps.

        Each scanned step is the same decomposition as ``forward_decode``
        — B_attn sub-batched attention, COMBINE (concatenate) into the
        full B_moe batch before each FFN/MoE — but the whole page compiles
        into ONE device program: sampled tokens self-feed on device and
        finished slots (``remaining`` exhausted) are masked.  The
        intra-forward yield points become trace-time boundaries only;
        the scheduler regains control at the page boundary, which is all
        §5.3 requires.  Returns ``(token_block, tokens, lengths,
        remaining, cache)`` with ``token_block`` of shape (steps, B);
        the carry outputs stay on device so pages decompose into chained
        pow2 chunks (see NodeEngine.decode_page).

        ``sampling=(sp, state)`` swaps the head argmax for the sampling
        pipeline (see models.transformer.decode_page) and appends the
        advanced per-slot state to the returned tuple.  ``lp_k`` (None |
        0 | K) swaps the raw token rows for the packed logprob plane of
        ``models.transformer.pack_logprob_block``.  ``flags`` is the
        static :class:`repro.sampling.SampleFlags` plan (sampled path);
        it is part of the executable cache key."""
        B = int(tokens.shape[0])
        n_sub = max(B // max(b_attn, 1), 1)
        key = (int(steps), n_sub, sampling is not None, lp_k, flags)
        fn = _lru_get(self._page_cache, key, _PAGE_JIT_CAP,
                      lambda: jax.jit(partial(self._page_impl,
                                              steps=int(steps),
                                              n_sub=n_sub,
                                              sampled=sampling is not None,
                                              lp_k=lp_k, flags=flags),
                                      donate_argnums=(0,)), self.owner)
        if sampling is None:
            return fn(cache, tokens, lengths, remaining)
        sp, state = sampling
        return fn(cache, tokens, lengths, remaining, sp, state)

    def _page_impl(self, cache, tokens, lengths, remaining, sp=None,
                   state=None, *, steps: int, n_sub: int,
                   sampled: bool = False, lp_k=None, flags=None):
        from repro.sampling import DEFAULT_FLAGS, sample_step
        flags = flags or DEFAULT_FLAGS

        cfg = self.cfg
        B = tokens.shape[0]
        slices = _sub_slices(B, n_sub)

        def model_step(cache, tokens, lengths):
            h = T._embed_tokens(cfg, self.params, tokens[:, None])

            def layer_body(hh, xs):
                p, c = xs
                h_parts, k_parts, v_parts = [], [], []
                for sl in slices:
                    hg, kg, vg = self._attn_impl(p, hh[sl], c["k"][sl],
                                                 c["v"][sl], lengths[sl],
                                                 n_sub)
                    h_parts.append(hg)
                    k_parts.append(kg)
                    v_parts.append(vg)
                hh = jnp.concatenate(h_parts, axis=0)   # COMBINE
                hh = self._ffn_impl(p, hh)
                return hh, {"k": jnp.concatenate(k_parts, axis=0),
                            "v": jnp.concatenate(v_parts, axis=0)}

            h, new_cache = jax.lax.scan(layer_body, h,
                                        (self.params["layers"], cache))
            return h, new_cache

        def emit(tokens, logits):
            return (tokens if lp_k is None
                    else T.pack_logprob_block(tokens, logits, lp_k))

        if not sampled:
            def one_step(carry, _):
                cache, tokens, lengths, remaining = carry
                h, new_cache = model_step(cache, tokens, lengths)
                if lp_k is None:
                    nxt, logits = self._head_impl(h), None
                else:
                    logits = self._head_logits_impl(h)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                live = remaining > 0
                tokens = jnp.where(live, nxt, tokens)
                lengths = lengths + live.astype(jnp.int32)
                remaining = remaining - live.astype(jnp.int32)
                return (new_cache, tokens, lengths, remaining), \
                    emit(tokens, logits)

            (cache, tokens, lengths, remaining), block = jax.lax.scan(
                one_step, (cache, tokens, lengths, remaining), None,
                length=steps)
            return block, tokens, lengths, remaining, cache

        def one_step(carry, _):
            cache, tokens, lengths, remaining, state = carry
            h, new_cache = model_step(cache, tokens, lengths)
            logits = self._head_logits_impl(h)
            if lp_k is None:
                nxt, live, remaining, state = sample_step(
                    logits, remaining, state, sp, flags)
            else:
                nxt, live, remaining, state, lanes = sample_step(
                    logits, remaining, state, sp, flags, lp_k=lp_k)
            tokens = jnp.where(live, nxt, tokens)
            lengths = lengths + live.astype(jnp.int32)
            out = (tokens if lp_k is None
                   else T.pack_plane_from_lanes(tokens, lanes))
            return (new_cache, tokens, lengths, remaining, state), out

        (cache, tokens, lengths, remaining, state), block = jax.lax.scan(
            one_step, (cache, tokens, lengths, remaining, state), None,
            length=steps)
        return block, tokens, lengths, remaining, cache, state

    def expert_load(self, b_moe: int) -> Dict[str, float]:
        """Per-expert batch statistics at the MoE gate for a combined batch
        of b_moe tokens (Fig. 2b quantity)."""
        cfg = self.cfg
        if not cfg.is_moe:
            return {"per_expert": float(b_moe), "experts": 1}
        per = b_moe * cfg.experts_per_token / cfg.num_experts
        return {"per_expert": per, "experts": cfg.num_experts}
