"""Unified model composition for all assigned architectures.

One scanned-stack LM covering: dense (llama/qwen/smollm/danube), MoE
(phi3.5-moe, qwen3-moe), MLA+MoE (deepseek-r1, the paper's own model), SSM
(mamba2), hybrid (recurrentgemma rec-rec-attn units), enc-dec audio
(whisper backbone) and VLM (pixtral backbone).  Layers are stacked with a
leading L dim and executed with ``jax.lax.scan`` (compact HLO, fast AOT
compile — see DESIGN.md §6); `jax.checkpoint` wraps the body for training.

The module exposes the functional surface consumed by launch/steps.py and by
the coroutine runtime:
    init_params, forward_loss, prefill, decode_step, init_cache,
    param_specs, cache_specs, batch_specs, param_count
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.api import MeshAxes, ModelConfig
from repro.models import layers, moe as moe_lib, rglru, ssm as ssm_lib

AUX_COEF = 0.01
CE_CHUNK = 512


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // 16) * 16


def _pdt(cfg):
    return jnp.dtype(cfg.dtype)


def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _hybrid_counts(cfg: ModelConfig):
    """(#full units, #tail rec layers) for the hybrid block pattern."""
    unit = len(cfg.block_pattern)
    return cfg.num_layers // unit, cfg.num_layers % unit


# ---------------------------------------------------------------------------
# per-kind layer init
# ---------------------------------------------------------------------------


def _init_dense_layer(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    return {"ln1": layers.init_norm(cfg), "attn": layers.init_attention(cfg, k1),
            "ln2": layers.init_norm(cfg), "mlp": layers.init_mlp(cfg, k2)}


def _init_moe_layer(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    attn = (layers.init_mla(cfg, k1) if cfg.use_mla
            else layers.init_attention(cfg, k1))
    return {"ln1": layers.init_norm(cfg), "attn": attn,
            "ln2": layers.init_norm(cfg), "moe": moe_lib.init_moe(cfg, k2)}


def _init_ssm_layer(cfg: ModelConfig, key):
    return {"ln1": layers.init_norm(cfg), "ssm": ssm_lib.init_ssm(cfg, key)}


def _init_rg_sublayer(cfg: ModelConfig, key, kind: str):
    k1, k2 = jax.random.split(key)
    if kind == "rec":
        t = rglru.init_rglru(cfg, k1)
    else:
        t = layers.init_attention(cfg, k1)
    return {"ln1": layers.init_norm(cfg), "t": t,
            "ln2": layers.init_norm(cfg), "mlp": layers.init_mlp(cfg, k2)}


def _init_rg_unit(cfg: ModelConfig, key):
    ks = jax.random.split(key, len(cfg.block_pattern))
    return {f"b{i}": _init_rg_sublayer(cfg, ks[i], kind)
            for i, kind in enumerate(cfg.block_pattern)}


def _init_enc_layer(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    return {"ln1": layers.init_norm(cfg), "attn": layers.init_attention(cfg, k1),
            "ln2": layers.init_norm(cfg), "mlp": layers.init_mlp(cfg, k2)}


def _init_dec_layer(cfg: ModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": layers.init_norm(cfg), "attn": layers.init_attention(cfg, k1),
            "ln2": layers.init_norm(cfg), "xattn": layers.init_attention(cfg, k2),
            "ln3": layers.init_norm(cfg), "mlp": layers.init_mlp(cfg, k3)}


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dt = _pdt(cfg)
    V, D = padded_vocab(cfg), cfg.d_model
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (V, D)) * 0.01).astype(dt),
        "lm_head": (jax.random.normal(ks[1], (D, V)) / math.sqrt(D)).astype(dt),
        "final_norm": layers.init_norm(cfg),
    }
    if cfg.family in ("dense", "vlm"):
        params["layers"] = _stack_init(partial(_init_dense_layer, cfg), ks[2],
                                       cfg.num_layers)
    elif cfg.family == "moe":
        params["layers"] = _stack_init(partial(_init_moe_layer, cfg), ks[2],
                                       cfg.num_layers)
    elif cfg.family == "ssm":
        params["layers"] = _stack_init(partial(_init_ssm_layer, cfg), ks[2],
                                       cfg.num_layers)
    elif cfg.family == "hybrid":
        n_units, n_tail = _hybrid_counts(cfg)
        params["units"] = _stack_init(partial(_init_rg_unit, cfg), ks[2], n_units)
        if n_tail:
            params["tail"] = _stack_init(
                partial(_init_rg_sublayer, cfg, kind="rec"), ks[3], n_tail)
    elif cfg.family == "audio":
        params["enc_layers"] = _stack_init(partial(_init_enc_layer, cfg), ks[2],
                                           cfg.encoder_layers)
        params["enc_norm"] = layers.init_norm(cfg)
        params["layers"] = _stack_init(partial(_init_dec_layer, cfg), ks[3],
                                       cfg.num_layers)
    if cfg.family in ("audio", "vlm"):
        params["adapter"] = (jax.random.normal(ks[4], (D, D)) / math.sqrt(D)).astype(dt)
    return params


# ---------------------------------------------------------------------------
# layer forward (full sequence) — returns (h, aux, cache_entry)
# ---------------------------------------------------------------------------


def _layer_fwd(cfg: ModelConfig, axes: MeshAxes, p, h, positions, hint,
               want_cache: bool):
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        if want_cache:
            y, cache = ssm_lib.ssm_fwd(cfg, p["ssm"], layers.apply_norm(cfg, p["ln1"], h),
                                       return_state=True)
        else:
            y = ssm_lib.ssm_fwd(cfg, p["ssm"], layers.apply_norm(cfg, p["ln1"], h))
            cache = None
        return h + y, aux, cache

    if cfg.family == "hybrid":
        raise RuntimeError("hybrid layers handled by _rg_unit_fwd")

    # attention
    xn = layers.apply_norm(cfg, p["ln1"], h)
    if cfg.use_mla:
        attn_out, kv = layers.mla_fwd(cfg, p["attn"], xn, positions)
        cache = {"ckv": kv[0], "kr": kv[1]} if want_cache else None
    else:
        attn_out, kv = layers.attention_fwd(
            cfg, p["attn"], xn, positions, use_rope=cfg.family != "audio",
            causal=True, shard_hint=hint)
        if not want_cache:
            cache = None
        elif cfg.sliding_window > 0:
            cache = _to_ring(cfg, kv[0], kv[1], positions, cfg.sliding_window)
        else:
            cache = {"k": kv[0], "v": kv[1]}
    h = h + attn_out

    # ffn
    xn = layers.apply_norm(cfg, p["ln2"], h)
    if cfg.is_moe:
        y, aux = moe_lib.moe_fwd(cfg, axes, p["moe"], xn)
    else:
        y = layers.mlp_fwd(cfg, p["mlp"], xn)
    return h + y, aux, cache


def _rg_sub_fwd(cfg, axes, p, h, positions, hint, want_cache, kind):
    xn = layers.apply_norm(cfg, p["ln1"], h)
    if kind == "rec":
        if want_cache:
            y, cache = rglru.rglru_fwd(cfg, p["t"], xn, return_state=True)
        else:
            y, cache = rglru.rglru_fwd(cfg, p["t"], xn), None
    else:
        y, kv = layers.attention_fwd(cfg, p["t"], xn, positions,
                                     window=cfg.local_window, shard_hint=hint)
        cache = None
        if want_cache:
            # convert to ring layout of size local_window
            cache = _to_ring(cfg, kv[0], kv[1], positions, cfg.local_window)
    h = h + y
    h = h + layers.mlp_fwd(cfg, p["mlp"], layers.apply_norm(cfg, p["ln2"], h))
    return h, cache


def _to_ring(cfg, k, v, positions, window):
    """Fold full (B,S,Hkv,dh) KV into a ring cache of size `window`."""
    B, S = k.shape[0], k.shape[1]
    Wc = min(window, S)
    k_r, v_r = k[:, S - Wc:], v[:, S - Wc:]
    pos_r = positions[:, S - Wc:]
    # ring layout: slot = pos % Wc
    slot = pos_r % Wc
    k_ring = jnp.zeros_like(k_r).at[jnp.arange(B)[:, None], slot].set(k_r)
    v_ring = jnp.zeros_like(v_r).at[jnp.arange(B)[:, None], slot].set(v_r)
    pos_ring = jnp.full((B, Wc), -1, jnp.int32).at[
        jnp.arange(B)[:, None], slot].set(pos_r)
    return {"k": k_ring, "v": v_ring, "pos": pos_ring}


def _rg_unit_fwd(cfg, axes, p, h, positions, hint, want_cache):
    caches = {}
    for i, kind in enumerate(cfg.block_pattern):
        h, c = _rg_sub_fwd(cfg, axes, p[f"b{i}"], h, positions, hint,
                           want_cache, kind)
        if want_cache:
            caches[f"b{i}"] = c
    return h, caches


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def _stack_fwd(cfg, axes, stack, h, positions, hint, want_cache, remat,
               fwd_fn=None, unroll=False):
    fwd_fn = fwd_fn or (lambda p, hh: _layer_fwd(cfg, axes, p, hh, positions,
                                                 hint, want_cache))

    def body(carry, lp):
        hh, aux = carry
        hh = _pin(axes, hh)
        out = fwd_fn(lp, hh)
        if len(out) == 3:
            hh2, a, cache = out
        else:
            hh2, cache = out
            a = jnp.zeros((), jnp.float32)
        return (hh2, aux + a), cache

    if remat:
        body = jax.checkpoint(body)
    (h, aux), caches = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                    stack, unroll=unroll)
    return h, aux, caches


def _pin(axes: MeshAxes, h):
    """Keep the residual stream sharded (batch over DP axes, replicated TP)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return h
    return jax.lax.with_sharding_constraint(h, P(axes.batch, None, None))


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "hybrid":          # gemma convention
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    return h


def _assemble_inputs(cfg, params, batch):
    """Merge frontend stub embeddings with token embeddings."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    h = _embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm" and "patches" in batch:
        pe = jnp.einsum("bpd,de->bpe", batch["patches"], params["adapter"])
        h = jnp.concatenate([pe.astype(h.dtype), h], axis=1)
    S = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.family == "audio":
        h = h + layers.sinusoid_pos(positions, cfg.d_model, h.dtype)
    return h, positions


def _encode(cfg, axes, params, frames, hint, remat, unroll=False):
    """Whisper-style encoder over stub frame embeddings (B, enc_seq, D)."""
    h = jnp.einsum("bsd,de->bse", frames, params["adapter"]).astype(_pdt(cfg))
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    h = h + layers.sinusoid_pos(positions, cfg.d_model, h.dtype)

    def enc_layer(p, hh):
        xn = layers.apply_norm(cfg, p["ln1"], hh)
        a, _ = layers.attention_fwd(cfg, p["attn"], xn, positions, causal=False,
                                    use_rope=False, shard_hint=hint)
        hh = hh + a
        hh = hh + layers.mlp_fwd(cfg, p["mlp"],
                                 layers.apply_norm(cfg, p["ln2"], hh))
        return hh, None

    h, _, _ = _stack_fwd(cfg, axes, params["enc_layers"], h, positions, hint,
                         False, remat, fwd_fn=enc_layer, unroll=unroll)
    return layers.apply_norm(cfg, params["enc_norm"], h), positions


def _dec_layer_fwd(cfg, axes, p, h, positions, enc, enc_pos, hint, want_cache):
    xn = layers.apply_norm(cfg, p["ln1"], h)
    a, kv = layers.attention_fwd(cfg, p["attn"], xn, positions, causal=True,
                                 use_rope=False, shard_hint=hint)
    h = h + a
    xk, xv = layers.kv_from_states(cfg, p["xattn"], enc)
    xn = layers.apply_norm(cfg, p["ln2"], h)
    a, _ = layers.attention_fwd(cfg, p["xattn"], xn, positions, causal=False,
                                use_rope=False, kv=(xk, xv), kv_positions=enc_pos,
                                shard_hint=None)
    h = h + a
    h = h + layers.mlp_fwd(cfg, p["mlp"], layers.apply_norm(cfg, p["ln3"], h))
    cache = {"k": kv[0], "v": kv[1], "xk": xk, "xv": xv} if want_cache else None
    return h, cache


def logits_fn(cfg, params, h):
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"]).astype(jnp.float32)
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _chunked_ce(cfg, params, h, labels, unroll=False):
    """Cross-entropy without materializing (B,S,V): scan over S chunks."""
    B, S, D = h.shape
    V = padded_vocab(cfg)
    c = min(CE_CHUNK, S)
    nc = S // c
    hc = jnp.moveaxis(h.reshape(B, nc, c, D), 1, 0)
    yc = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)

    def step(carry, xs):
        tot, cnt = carry
        hh, yy = xs
        logits = logits_fn(cfg, params, hh)                     # (B,c,V) f32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        oh = jax.nn.one_hot(jnp.maximum(yy, 0), V, dtype=jnp.float32)
        ll = jnp.sum(logits * oh, axis=-1)
        valid = (yy >= 0).astype(jnp.float32)
        return (tot + jnp.sum((lse - ll) * valid), cnt + jnp.sum(valid)), None

    # checkpoint: CE backward recomputes per-chunk logits instead of
    # stashing (B,c,V) fp32 per chunk (DESIGN.md §6).
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(step),
                                 (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.float32)), (hc, yc),
                                 unroll=unroll)
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# public: forward_loss / prefill / decode_step
# ---------------------------------------------------------------------------


def _backbone(cfg, axes, params, batch, hint, want_cache, remat,
              unroll=False):
    """Shared trunk: inputs -> final hidden states (+aux, +caches)."""
    h, positions = _assemble_inputs(cfg, params, batch)
    enc = None
    if cfg.family == "audio":
        enc, enc_pos = _encode(cfg, axes, params, batch["frames"], hint, remat,
                               unroll=unroll)

        def dec_fn(p, hh):
            return _dec_layer_fwd(cfg, axes, p, hh, positions, enc, enc_pos,
                                  hint, want_cache)

        h, aux, caches = _stack_fwd(cfg, axes, params["layers"], h, positions,
                                    hint, want_cache, remat, fwd_fn=dec_fn,
                                    unroll=unroll)
    elif cfg.family == "hybrid":
        def unit_fn(p, hh):
            return _rg_unit_fwd(cfg, axes, p, hh, positions, hint, want_cache)

        h, _, ucaches = _stack_fwd(cfg, axes, params["units"], h, positions,
                                   hint, want_cache, remat, fwd_fn=unit_fn,
                                   unroll=unroll)
        caches = {"units": ucaches}
        aux = jnp.zeros((), jnp.float32)
        if "tail" in params:
            def tail_fn(p, hh):
                return _rg_sub_fwd(cfg, axes, p, hh, positions, hint,
                                   want_cache, "rec")

            h, _, tcaches = _stack_fwd(cfg, axes, params["tail"], h, positions,
                                       hint, want_cache, remat, fwd_fn=tail_fn,
                                       unroll=unroll)
            caches["tail"] = tcaches
    else:
        h, aux, caches = _stack_fwd(cfg, axes, params["layers"], h, positions,
                                    hint, want_cache, remat, unroll=unroll)
    h = layers.apply_norm(cfg, params["final_norm"], h)
    return h, aux, caches


def forward_loss(cfg: ModelConfig, axes: MeshAxes, params, batch, *,
                 hint=None, remat=True, unroll=False):
    """Training loss (chunked CE + MoE aux)."""
    h, aux, _ = _backbone(cfg, axes, params, batch, hint, False, remat,
                          unroll=unroll)
    loss = _chunked_ce(cfg, params, h, batch["labels"], unroll=unroll)
    if cfg.is_moe:
        loss = loss + AUX_COEF * aux / max(cfg.num_layers, 1)
    return loss


def prefill(cfg: ModelConfig, axes: MeshAxes, params, batch, *, hint=None,
            unroll=False):
    """Prefill: returns (last-position logits, cache pytree)."""
    h, _, caches = _backbone(cfg, axes, params, batch, hint, True, False,
                             unroll=unroll)
    logits = logits_fn(cfg, params, h[:, -1:, :])
    return logits, caches


def decode_step(cfg: ModelConfig, axes: MeshAxes, params, cache, tokens,
                lengths, unroll=False):
    """One greedy decode step.  tokens (B,), lengths (B,) ->
    (next_tokens, cache)."""
    logits, new_cache = decode_step_logits(cfg, axes, params, cache, tokens,
                                           lengths, unroll=unroll)
    next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tokens, new_cache


def decode_step_logits(cfg: ModelConfig, axes: MeshAxes, params, cache,
                       tokens, lengths, unroll=False):
    """One decode step returning the raw next-token logits (B, V) so the
    caller picks the token (argmax or the sampling pipeline)."""
    B = tokens.shape[0]
    h = _embed_tokens(cfg, params, tokens[:, None])
    if cfg.family == "audio":
        h = h + layers.sinusoid_pos(lengths[:, None], cfg.d_model, h.dtype)

    if cfg.family == "hybrid":
        def unit_dec(hh, xs):
            p, c = xs
            newc = {}
            for i, kind in enumerate(cfg.block_pattern):
                hh, newc[f"b{i}"] = _rg_sub_decode(cfg, p[f"b{i}"], hh,
                                                   c[f"b{i}"], lengths, kind)
            return hh, newc

        h, new_units = jax.lax.scan(unit_dec, h,
                                    (params["units"], cache["units"]),
                                    unroll=unroll)
        new_cache = {"units": new_units}
        if "tail" in cache:
            def tail_dec(hh, xs):
                p, c = xs
                hh, nc = _rg_sub_decode(cfg, p, hh, c, lengths, "rec")
                return hh, nc

            h, new_tail = jax.lax.scan(tail_dec, h,
                                       (params["tail"], cache["tail"]),
                                       unroll=unroll)
            new_cache["tail"] = new_tail
    else:
        def body(hh, xs):
            p, c = xs
            return _layer_decode(cfg, axes, p, c, hh, lengths)

        h, new_cache = jax.lax.scan(body, h, (params["layers"], cache),
                                    unroll=unroll)

    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = logits_fn(cfg, params, h)                           # (B,1,V)
    return logits[:, 0, :], new_cache


def _plane(tokens, chosen, vals, idx):
    """One step's int32 plane row block: token ids as they are, log-probs
    bitcast f32 -> int32.  The plane is int32, not f32, because a token id
    bitcast to f32 is a denormal, and the TPU flushes f32 denormals to
    zero on the way through the scan's output."""
    as_i32 = lambda x: jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                                    jnp.int32)
    parts = [tokens.astype(jnp.int32)[:, None], as_i32(chosen)[:, None]]
    if vals is not None:
        parts += [as_i32(vals), idx.astype(jnp.int32)]
    return jnp.concatenate(parts, axis=-1)


def pack_logprob_block(tokens, logits, lp_k: int):
    """Pack one decode step's (tokens, raw logits) into a single int32 row
    block so the whole page still moves in ONE device->host transfer.

    Layout along the last axis (width 2 + 2*lp_k):
      [0]                 tokens
      [1]                 log-softmax(logits)[token] — chosen-token
                          logprob, f32 bitcast to int32
      [2 : 2+K]           top-K logprob values (descending), f32 bitcast
      [2+K : 2+2K]        top-K token ids
    Unpacked host-side by ``unpack_logprob_block``."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen = jnp.take_along_axis(lp, tokens[:, None].astype(jnp.int32),
                                 axis=1)[:, 0]
    vals = idx = None
    if lp_k > 0:
        vals, idx = jax.lax.top_k(lp, lp_k)
    return _plane(tokens, chosen, vals, idx)


def pack_plane_from_lanes(tokens, lanes):
    """Assemble the ``pack_logprob_block`` layout from the lanes dict
    ``repro.sampling.sample_step`` returns (chosen_lp + top-K vals/ids),
    so the sampled megastep reuses the single fused-sampling pass for the
    transfer plane instead of paying a second full-vocab log_softmax +
    top_k.  Layout-identical to ``pack_logprob_block``."""
    return _plane(tokens, lanes["chosen_lp"], lanes["top_vals"],
                  lanes["top_idx"])


def unpack_logprob_block(block_np):
    """Inverse of ``pack_logprob_block`` for a (steps, B, 2+2K) host array.
    Returns (tokens (steps,B) i32, chosen_lp (steps,B) f32,
    topk_vals (steps,B,K) f32 | None, topk_ids (steps,B,K) i32 | None)."""
    import numpy as np
    K = (block_np.shape[-1] - 2) // 2
    as_f32 = lambda x: np.ascontiguousarray(x).view(np.float32)
    tokens = block_np[..., 0]
    chosen = as_f32(block_np[..., 1])
    if K == 0:
        return tokens, chosen, None, None
    return tokens, chosen, as_f32(block_np[..., 2:2 + K]), \
        block_np[..., 2 + K:]


def decode_page(cfg: ModelConfig, axes: MeshAxes, params, cache, tokens,
                lengths, remaining, steps: int, unroll=False,
                sampling=None, lp_k=None, flags=None):
    """Fused decode megastep: `steps` decode steps in ONE program.

    A ``lax.scan`` over ``decode_step`` that keeps tokens/lengths/KV on
    device, self-feeds the sampled token, and masks all per-slot updates
    once ``remaining`` hits zero (mid-page finishes).  Slots whose
    ``remaining`` starts at zero (empty or already-finished) never advance:
    their KV writes land at position ``lengths`` — one past their valid
    region — and are overwritten/ignored, exactly as in the per-step loop.

    tokens/lengths/remaining: (B,) int32.  Returns
    ``(token_block, tokens, lengths, remaining, cache)`` where
    ``token_block`` is (steps, B) — row t is the slot's token after step t
    (rows past a slot's remaining repeat its last token and must be
    discarded by the caller).  One host transfer of ``token_block``
    replaces ``steps`` per-token round-trips.

    With ``sampling=(sp, state)`` (pack_params row arrays + the per-slot
    PRNG/penalty state from repro.sampling) each step runs the full logit
    pipeline and a Gumbel-max categorical draw instead of argmax, the
    state rides the scan carry (split-free fold_in keys, so masked steps
    never perturb a live slot's stream), and stop-token hits zero the
    slot's ``remaining`` on device.  Returns the same tuple plus the
    advanced ``state`` appended.

    With ``lp_k`` set (0 = chosen-token only, K > 0 = also the top-K
    alternatives) each step's output row is the packed
    ``pack_logprob_block`` plane — (steps, B, 2+2K) int32 — built from the
    RAW (pre-sampling-pipeline) model logits, so logprobs ride the
    page's one transfer and report pre-filter values even under
    top-k/top-p sampling.

    ``flags`` (a static :class:`repro.sampling.SampleFlags`, sampled
    path only) bakes the host-decided sampling plan into the executable:
    XLA shared-sort tier vs the Pallas fused kernel, and whether the
    penalty state ops run at all.  On the sampled+logprobs path the
    logprob lanes come out of the same fused-sampling pass.
    """
    if sampling is None:
        if lp_k is None:
            def body(carry, _):
                cache, tokens, lengths, remaining = carry
                nxt, cache = decode_step(cfg, axes, params, cache, tokens,
                                         lengths, unroll=unroll)
                live = remaining > 0
                tokens = jnp.where(live, nxt, tokens)
                lengths = lengths + live.astype(jnp.int32)
                remaining = remaining - live.astype(jnp.int32)
                return (cache, tokens, lengths, remaining), tokens
        else:
            def body(carry, _):
                cache, tokens, lengths, remaining = carry
                logits, cache = decode_step_logits(cfg, axes, params, cache,
                                                   tokens, lengths,
                                                   unroll=unroll)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                live = remaining > 0
                tokens = jnp.where(live, nxt, tokens)
                lengths = lengths + live.astype(jnp.int32)
                remaining = remaining - live.astype(jnp.int32)
                return (cache, tokens, lengths, remaining), \
                    pack_logprob_block(tokens, logits, lp_k)

        (cache, tokens, lengths, remaining), block = jax.lax.scan(
            body, (cache, tokens, lengths, remaining), None, length=steps)
        return block, tokens, lengths, remaining, cache

    from repro.sampling import DEFAULT_FLAGS, sample_step
    sp, state = sampling
    flags = flags or DEFAULT_FLAGS

    def body(carry, _):
        cache, tokens, lengths, remaining, state = carry
        logits, cache = decode_step_logits(cfg, axes, params, cache, tokens,
                                           lengths, unroll=unroll)
        if lp_k is None:
            nxt, live, remaining, state = sample_step(logits, remaining,
                                                      state, sp, flags)
            lanes = None
        else:
            nxt, live, remaining, state, lanes = sample_step(
                logits, remaining, state, sp, flags, lp_k=lp_k)
        tokens = jnp.where(live, nxt, tokens)
        lengths = lengths + live.astype(jnp.int32)
        out = (tokens if lp_k is None
               else pack_plane_from_lanes(tokens, lanes))
        return (cache, tokens, lengths, remaining, state), out

    (cache, tokens, lengths, remaining, state), block = jax.lax.scan(
        body, (cache, tokens, lengths, remaining, state), None, length=steps)
    return block, tokens, lengths, remaining, cache, state


def _layer_decode(cfg, axes, p, c, h, lengths):
    if cfg.family == "ssm":
        y, nc = ssm_lib.ssm_decode(cfg, p["ssm"],
                                   layers.apply_norm(cfg, p["ln1"], h), c)
        return h + y, nc

    xn = layers.apply_norm(cfg, p["ln1"], h)
    if cfg.use_mla:
        a, ckv, kr = layers.mla_decode(cfg, p["attn"], xn, c["ckv"], c["kr"],
                                       lengths)
        nc = {"ckv": ckv, "kr": kr}
    elif cfg.sliding_window > 0 and "pos" in c:
        a, k, v, pos = layers.attention_decode_ring(
            cfg, p["attn"], xn, c["k"], c["v"], c["pos"], lengths)
        nc = {"k": k, "v": v, "pos": pos}
    else:
        a, k, v = layers.attention_decode(cfg, p["attn"], xn, c["k"], c["v"],
                                          lengths,
                                          use_rope=cfg.family != "audio",
                                          axes=axes)
        nc = {"k": k, "v": v}
    h = h + a

    if cfg.family == "audio":
        xn = layers.apply_norm(cfg, p["ln2"], h)
        q = jnp.einsum("bsd,dhk->bshk", xn, p["xattn"]["wq"])
        enc_len = jnp.full((h.shape[0],), c["xk"].shape[1], jnp.int32)
        o = layers.decode_attention(q, c["xk"], c["xv"], enc_len)
        h = h + jnp.einsum("bshk,hkd->bsd", o, p["xattn"]["wo"])
        nc.update({"xk": c["xk"], "xv": c["xv"]})
        xn = layers.apply_norm(cfg, p["ln3"], h)
        h = h + layers.mlp_fwd(cfg, p["mlp"], xn)
        return h, nc

    xn = layers.apply_norm(cfg, p["ln2"], h)
    if cfg.is_moe:
        y, _ = moe_lib.moe_fwd(cfg, axes, p["moe"], xn)
    else:
        y = layers.mlp_fwd(cfg, p["mlp"], xn)
    return h + y, nc


def _rg_sub_decode(cfg, p, h, c, lengths, kind):
    xn = layers.apply_norm(cfg, p["ln1"], h)
    if kind == "rec":
        y, nc = rglru.rglru_decode(cfg, p["t"], xn, c)
    else:
        y, k, v, pos = layers.attention_decode_ring(
            cfg, p["t"], xn, c["k"], c["v"], c["pos"], lengths,
            window=cfg.local_window)
        nc = {"k": k, "v": v, "pos": pos}
    h = h + y
    h = h + layers.mlp_fwd(cfg, p["mlp"], layers.apply_norm(cfg, p["ln2"], h))
    return h, nc


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    """Decode cache sized for `max_len` context (SWA archs: ring of window)."""
    dt = _pdt(cfg)
    L = cfg.num_layers
    Hkv, dh = cfg.num_kv_heads, cfg.head_dim
    if cfg.family == "ssm":
        one = ssm_lib.init_ssm_cache(cfg, B, dt)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), one)
    if cfg.family == "hybrid":
        n_units, n_tail = _hybrid_counts(cfg)
        Wc = min(cfg.local_window, max_len)

        def unit_cache():
            d = {}
            for i, kind in enumerate(cfg.block_pattern):
                if kind == "rec":
                    d[f"b{i}"] = rglru.init_rglru_cache(cfg, B, dt)
                else:
                    d[f"b{i}"] = {"k": jnp.zeros((B, Wc, Hkv, dh), dt),
                                  "v": jnp.zeros((B, Wc, Hkv, dh), dt),
                                  "pos": jnp.full((B, Wc), -1, jnp.int32)}
            return d

        cache = {"units": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_units,) + x.shape), unit_cache())}
        if n_tail:
            one = rglru.init_rglru_cache(cfg, B, dt)
            cache["tail"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_tail,) + x.shape), one)
        return cache
    if cfg.use_mla:
        return {"ckv": jnp.zeros((L, B, max_len, cfg.kv_lora_rank), dt),
                "kr": jnp.zeros((L, B, max_len, cfg.rope_head_dim), dt)}
    if cfg.family == "audio":
        enc = cfg.encoder_seq
        return {"k": jnp.zeros((L, B, max_len, Hkv, dh), dt),
                "v": jnp.zeros((L, B, max_len, Hkv, dh), dt),
                "xk": jnp.zeros((L, B, enc, Hkv, dh), dt),
                "xv": jnp.zeros((L, B, enc, Hkv, dh), dt)}
    if cfg.sliding_window > 0:
        Wc = min(cfg.sliding_window, max_len)
        return {"k": jnp.zeros((L, B, Wc, Hkv, dh), dt),
                "v": jnp.zeros((L, B, Wc, Hkv, dh), dt),
                "pos": jnp.full((L, B, Wc), -1, jnp.int32)}
    return {"k": jnp.zeros((L, B, max_len, Hkv, dh), dt),
            "v": jnp.zeros((L, B, max_len, Hkv, dh), dt)}


# ---------------------------------------------------------------------------
# parameter counting (for MODEL_FLOPS)
# ---------------------------------------------------------------------------


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: init_params(cfg, key))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = math.prod(leaf.shape)
        names = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
        if active_only and "moe" in names:
            name = names[-1]
            if name in ("w1", "w2", "w3"):
                n = n // cfg.num_experts * cfg.experts_per_token
        total += n
    return total
