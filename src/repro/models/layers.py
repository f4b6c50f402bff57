"""Core neural layers: norms, RoPE, GQA/MLA attention, gated MLPs.

Conventions
-----------
* All functions are pure; params are plain dicts of jnp arrays.
* Activations flow in ``cfg.dtype`` (bf16 by default); softmax statistics and
  norm reductions are in fp32.
* q heads are FLAT (B, S, H, dh); k/v are grouped (B, S, Hkv, dh) and
  expanded per KV-chunk inside the attention loop.  This allows q heads to be
  TP-sharded while small GQA kv head counts stay replicated, and lets archs
  whose head count does not divide the model axis fall back to
  sequence-sharded (SP) attention (see distributed/sharding.py).
* Full-sequence attention uses a chunked online-softmax (flash-style) in pure
  JAX so dry-run lowering is memory-honest (O(S*chunk) logits, never O(S^2)).
  The Pallas kernels in ``repro.kernels`` implement the same contract for the
  TPU hot path and are validated against these functions.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.api import ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(dt) * w


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * w + b


def init_norm(cfg: ModelConfig, d=None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"w": jnp.ones((d,), _pdt(cfg))}
    return {"w": jnp.ones((d,), _pdt(cfg)), "b": jnp.zeros((d,), _pdt(cfg))}


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


def _pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# RoPE (rotate-half / neox convention)
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float, rot_dim: Optional[int] = None):
    """x: (B, S, ..., dh); positions: (B, S)."""
    dh = x.shape[-1]
    rot = rot_dim or dh
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv          # (B, S, half)
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., :half], xr[..., half:]
    r = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    r = r.astype(x.dtype)
    return jnp.concatenate([r, x[..., rot:]], -1) if rot < dh else r


def sinusoid_pos(positions, d, dtype):
    """Whisper-style sinusoidal positional embedding. positions (B,S) -> (B,S,d)."""
    half = d // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (flash-style, pure JAX)
# ---------------------------------------------------------------------------


def chunked_attention(
    q,                      # (B, Sq, H, dh)   flat q heads
    k,                      # (B, Skv, Hkv, dh)
    v,                      # (B, Skv, Hkv, dv)
    q_positions,            # (B, Sq) int32
    kv_positions,           # (B, Skv) int32
    *,
    causal: bool = True,
    window: int = 0,        # 0 = unbounded
    chunk: int = 512,
    softcap: float = 0.0,
):
    """Flash-style chunked attention (memory-optimal fwd AND bwd).

    Delegates to models.flash: a custom-VJP online softmax whose backward
    recomputes per-chunk logits instead of saving them (DESIGN.md §6).
    """
    from repro.models import flash

    Skv = k.shape[1]
    chunk = min(chunk, Skv)
    assert Skv % chunk == 0, (Skv, chunk)
    opts = (bool(causal), int(window), int(chunk), float(softcap))
    return flash.flash_attention(opts, q, k, v, q_positions, kv_positions)


def decode_attention(
    q,                      # (B, 1, H, dh)
    k_cache,                # (B, S, Hkv, dh)  (sequence dim may be sharded)
    v_cache,
    lengths,                # (B,) number of valid cache positions
    *,
    window: int = 0,
    softcap: float = 0.0,
):
    """One-token attention against a dense cache.

    Whole-cache einsum + masked softmax: under pjit with the cache sequence
    dimension sharded over ``model`` this partitions into flash-decoding
    (partial softmax per shard + all-reduce of max/sum).
    """
    B, _, H, dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, dh)
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = pos < lengths[:, None]
    if window > 0:
        mask &= pos > (lengths[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def decode_attention_ring(
    q,                      # (B, 1, H, dh)
    k_cache,                # (B, Wc, Hkv, dh) ring buffer
    v_cache,
    pos_cache,              # (B, Wc) int32 positions stored per slot (-1 = empty)
    lengths,                # (B,) current position (inclusive of new token)
    *,
    window: int,
    softcap: float = 0.0,
):
    """Sliding-window decode against a ring-buffer cache (SWA archs)."""
    B, _, H, dh = q.shape
    Wc, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, dh)
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    mask = (pos_cache >= 0) & (pos_cache < lengths[:, None]) \
        & (pos_cache > (lengths[:, None] - 1 - window))
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def attention_decode_ring(cfg: ModelConfig, p, x, k_cache, v_cache, pos_cache,
                          lengths, *, window=None, use_rope=True):
    """One-token SWA decode with ring-buffer cache of size min(window, S)."""
    w = cfg.sliding_window if window is None else window
    positions = lengths[:, None]
    q, k, v = attention_qkv(cfg, p, x, positions, use_rope=use_rope)
    Wc = k_cache.shape[1]
    slot = lengths % Wc
    k_cache = cache_update(k_cache, k, slot)
    v_cache = cache_update(v_cache, v, slot)
    oh = jax.nn.one_hot(slot, Wc, dtype=jnp.int32)
    pos_cache = pos_cache * (1 - oh) + oh * lengths[:, None]
    o = decode_attention_ring(q, k_cache, v_cache, pos_cache, lengths + 1,
                              window=w, softcap=cfg.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, k_cache, v_cache, pos_cache


def cache_update(cache, new, lengths, axes=None):
    """Write ``new`` (B, 1, Hkv, dh) at position ``lengths``.

    Single-device / no-mesh: per-row ``dynamic_update_slice`` (vmapped) —
    O(1) HBM traffic per step.  This replaced the one-hot mix, which cost
    a full O(S) cache read+write per layer per step; writes whose index
    falls outside [0, S) are dropped, matching the old one-hot semantics.
    With ``axes`` (decode regime, cache sequence dim sharded over
    ``model``): shard_map + per-shard dynamic-update-slice — only the shard
    owning position ``lengths`` writes one token (§Perf D1).
    """
    if axes is not None and axes.model is not None:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and axes.model in mesh.axis_names:
            return _cache_update_dus(cache, new, lengths, axes, mesh)
    return _cache_update_dus_local(cache, new, lengths)


def _cache_update_dus_local(cache, new, lengths):
    """Per-row DUS write: cache (B, S, ...), new (B, 1, ...), lengths (B,).
    Out-of-range rows (index < 0 or >= S) are no-op writes — DUS alone
    would clamp to S-1 and clobber the last position."""
    S = cache.shape[1]

    def row(c_row, n_row, i):
        zeros = (0,) * (c_row.ndim - 1)
        inb = (i >= 0) & (i < S)
        i_c = jnp.clip(i, 0, S - 1)
        cur = jax.lax.dynamic_slice(c_row, (i_c,) + zeros, n_row.shape)
        return jax.lax.dynamic_update_slice(
            c_row, jnp.where(inb, n_row, cur), (i_c,) + zeros)

    return jax.vmap(row)(cache, new, lengths)


def _cache_update_dus(cache, new, lengths, axes, mesh):
    import math as _math
    from functools import partial
    from jax.sharding import PartitionSpec as P

    dp = _math.prod(mesh.shape[a] for a in axes.batch) if axes.batch else 1
    Bax = axes.batch if cache.shape[0] % max(dp, 1) == 0 else None
    cspec = P(Bax, axes.model, None, None)
    bspec = P(Bax, None, None, None)
    lspec = P(Bax)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(cspec, bspec, lspec), out_specs=cspec,
             check_vma=False)
    def upd(c_l, n_l, len_l):
        m = jax.lax.axis_index(axes.model)
        S_l = c_l.shape[1]
        idx = len_l - m * S_l                       # (B_l,) local position

        def row(c_row, n_row, i):
            inb = (i >= 0) & (i < S_l)
            i_c = jnp.clip(i, 0, S_l - 1)
            cur = jax.lax.dynamic_slice(c_row, (i_c, 0, 0), n_row.shape)
            n_eff = jnp.where(inb, n_row, cur)      # no-op write off-shard
            return jax.lax.dynamic_update_slice(c_row, n_eff, (i_c, 0, 0))

        return jax.vmap(row)(c_l, n_l, idx)

    return upd(cache, new, lengths)


def _cache_update_2d(cache, new, lengths):
    """cache (B, S, R), new (B, 1, R): same per-row DUS write (the MLA
    latent cache shares the O(1)-traffic path)."""
    return _cache_update_dus_local(cache, new, lengths)


# ---------------------------------------------------------------------------
# GQA attention block (flat q heads)
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key):
    dt = _pdt(cfg)
    D, H, Hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(D)
    p = {
        "wq": (jax.random.normal(ks[0], (D, H, dh)) * sc).astype(dt),
        "wk": (jax.random.normal(ks[1], (D, Hkv, dh)) * sc).astype(dt),
        "wv": (jax.random.normal(ks[2], (D, Hkv, dh)) * sc).astype(dt),
        "wo": (jax.random.normal(ks[3], (H, dh, D))
               * sc / math.sqrt(max(cfg.num_layers, 1))).astype(dt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H, dh), dt)
        p["bk"] = jnp.zeros((Hkv, dh), dt)
        p["bv"] = jnp.zeros((Hkv, dh), dt)
    return p


def attention_qkv(cfg: ModelConfig, p, x, positions, *, use_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def kv_from_states(cfg: ModelConfig, p, states):
    """Compute (k, v) from encoder states (cross-attention source)."""
    k = jnp.einsum("bsd,dhk->bshk", states, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", states, p["wv"])
    if cfg.attn_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


def attention_fwd(cfg: ModelConfig, p, x, positions, *, causal=True, chunk=512,
                  use_rope=True, window=None, kv=None, kv_positions=None,
                  shard_hint=None):
    """Full-sequence attention; returns (out, (k, v)) for cache capture.

    kv/kv_positions: optional precomputed cross-attention source.
    shard_hint: optional fn applied to (q, k, v) to pin sharding (SP vs TP).
    """
    if kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.attn_bias:
            q = q + p["bq"]
        k, v = kv
        kv_pos = kv_positions
    else:
        q, k, v = attention_qkv(cfg, p, x, positions, use_rope=use_rope)
        kv_pos = positions
    if shard_hint is not None:
        q, k, v = shard_hint(q, k, v)
    w = cfg.sliding_window if window is None else window
    o = chunked_attention(q, k, v, positions, kv_pos, causal=causal,
                          window=w, chunk=chunk, softcap=cfg.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (k, v)


def attention_decode(cfg: ModelConfig, p, x, k_cache, v_cache, lengths, *,
                     use_rope=True, window=None, update_cache=True,
                     axes=None):
    """One-token decode; returns (out, new_k_cache, new_v_cache)."""
    positions = lengths[:, None]  # (B, 1)
    q, k, v = attention_qkv(cfg, p, x, positions, use_rope=use_rope)
    if update_cache:
        k_cache = cache_update(k_cache, k, lengths, axes=axes)
        v_cache = cache_update(v_cache, v, lengths, axes=axes)
    w = cfg.sliding_window if window is None else window
    o = decode_attention(q, k_cache, v_cache,
                         lengths + (1 if update_cache else 0),
                         window=w, softcap=cfg.logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-style latent KV) — used by the paper's own model
# ---------------------------------------------------------------------------


def init_mla(cfg: ModelConfig, key):
    dt = _pdt(cfg)
    D, H = cfg.d_model, cfg.num_heads
    r_q, r_kv, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_head_dim
    dn = cfg.head_dim
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(D)
    return {
        "wq_a": (jax.random.normal(ks[0], (D, r_q)) * sc).astype(dt),
        "q_norm": jnp.ones((r_q,), dt),
        "wq_b": (jax.random.normal(ks[1], (r_q, H, dn + dr)) / math.sqrt(r_q)).astype(dt),
        "wkv_a": (jax.random.normal(ks[2], (D, r_kv + dr)) * sc).astype(dt),
        "kv_norm": jnp.ones((r_kv,), dt),
        "wk_b": (jax.random.normal(ks[3], (r_kv, H, dn)) / math.sqrt(r_kv)).astype(dt),
        "wv_b": (jax.random.normal(ks[4], (r_kv, H, dn)) / math.sqrt(r_kv)).astype(dt),
        "wo": (jax.random.normal(ks[5], (H, dn, D)) * sc
               / math.sqrt(max(cfg.num_layers, 1))).astype(dt),
    }


def mla_project(cfg: ModelConfig, p, x, positions):
    dn = cfg.head_dim
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"])
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_fwd(cfg: ModelConfig, p, x, positions, *, chunk=512):
    """Prefill/train path: decompress latent KV and run standard MHA."""
    dr = cfg.rope_head_dim
    q_nope, q_rope, c_kv, k_rope = mla_project(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], -1)                    # (B,S,H,dn+dr)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], k_nope.shape[:3] + (dr,))
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    o = chunked_attention(q, k, v, positions, positions, causal=True, chunk=chunk)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (c_kv, k_rope)


def mla_decode(cfg: ModelConfig, p, x, ckv_cache, krope_cache, lengths):
    """Absorbed decode: attention in latent space; cache = (c_kv, k_rope)."""
    q_nope, q_rope, c_kv, k_rope = mla_project(cfg, p, x, lengths[:, None])
    ckv_cache = _cache_update_2d(ckv_cache, c_kv, lengths)
    krope_cache = _cache_update_2d(krope_cache, k_rope, lengths)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    scale = 1.0 / math.sqrt(cfg.head_dim + cfg.rope_head_dim)
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat.astype(jnp.float32),
                    ckv_cache.astype(jnp.float32))
         + jnp.einsum("bqhk,bsk->bhqs", q_rope.astype(jnp.float32),
                      krope_cache.astype(jnp.float32))) * scale
    S = ckv_cache.shape[1]
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < (lengths + 1)[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", pattn,
                       ckv_cache.astype(jnp.float32)).astype(x.dtype)
    o = jnp.einsum("bqhr,rhk->bqhk", o_lat, p["wv_b"])
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, ckv_cache, krope_cache


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff=None):
    dt = _pdt(cfg)
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w1": (jax.random.normal(ks[0], (D, F)) / math.sqrt(D)).astype(dt),
        "w3": (jax.random.normal(ks[1], (D, F)) / math.sqrt(D)).astype(dt),
        "w2": (jax.random.normal(ks[2], (F, D)) / math.sqrt(F)
               / math.sqrt(max(cfg.num_layers, 1))).astype(dt),
    }


def mlp_fwd(cfg: ModelConfig, p, x):
    act = jax.nn.silu if cfg.act == "silu" else (lambda u: jax.nn.gelu(u, approximate=True))
    g = act(jnp.einsum("bsd,df->bsf", x, p["w1"]))
    u = jnp.einsum("bsd,df->bsf", x, p["w3"])
    return jnp.einsum("bsf,fd->bsd", g * u, p["w2"])
