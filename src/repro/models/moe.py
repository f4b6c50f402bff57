"""Mixture-of-Experts layer with shard_map expert parallelism.

Design (see DESIGN.md §3/§4)
----------------------------
Experts are sharded over the ``model`` mesh axis (EP).  Activations enter the
MoE replicated over ``model`` (TP regime keeps the residual stream replicated
after each block's psum), so dispatch is a *local* capacity-bounded
gather per expert shard and combine is a single psum over ``model`` — the
TPU-native mapping of the paper's DeepEP all-to-all (tokens never move over
the wire; partial expert outputs are reduced instead).  An explicit
all-to-all variant for token-sharded (sequence-parallel) residual streams is
provided for the perf hillclimb.

Capacity follows the paper's fixed-plan model: ``C = ceil(T*k/E * cf)``
(static shape), overflowing tokens drop to the residual path — the runtime's
COMBINE primitive is the mechanism that keeps T large enough for E to be
well-fed (paper Fig. 2b).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.api import MeshAxes, ModelConfig
from repro.models import layers


def init_moe(cfg: ModelConfig, key):
    dt = jnp.dtype(cfg.dtype)
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "wg": (jax.random.normal(ks[0], (D, E)) / math.sqrt(D)).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (E, D, F)) / math.sqrt(D)).astype(dt),
        "w3": (jax.random.normal(ks[2], (E, D, F)) / math.sqrt(D)).astype(dt),
        "w2": (jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F)
               / math.sqrt(max(cfg.num_layers, 1))).astype(dt),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = layers.init_mlp(cfg, ks[4], d_ff=cfg.shared_d_ff)
    return p


def expert_capacity(cfg: ModelConfig, tokens: int) -> int:
    """Static per-expert slot count for a local token pool of size `tokens`."""
    c = math.ceil(tokens * cfg.experts_per_token / cfg.num_experts
                  * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor of 8 slots


def _route(cfg: ModelConfig, wg, xt):
    """Router: returns (vals (T,k) fp32, ids (T,k) int32, aux fp32 scalar)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wg)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    vals = vals / jnp.maximum(jnp.sum(vals, -1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    E = cfg.num_experts
    f = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(0, 1)) * E
    pbar = jnp.mean(probs, axis=0)
    aux = jnp.sum(f * pbar)
    return vals, ids, aux


def _expert_mlp(cfg: ModelConfig, p, xs):
    """xs: (El, C, D) -> (El, C, D)."""
    act = jax.nn.silu if cfg.act == "silu" else (lambda u: jax.nn.gelu(u, approximate=True))
    g = act(jnp.einsum("ecd,edf->ecf", xs, p["w1"]))
    u = jnp.einsum("ecd,edf->ecf", xs, p["w3"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["w2"])


def moe_fwd(cfg: ModelConfig, axes: MeshAxes, p, x):
    """Expert-parallel MoE over the current mesh. x: (B, S, D) -> (B, S, D), aux."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or axes.model is None or axes.model not in mesh.axis_names:
        y, aux = _moe_local(cfg, p, x)
    else:
        bspec = P(axes.batch, None, None)
        espec = P(axes.model, None, None)

        all_axes = tuple(mesh.axis_names)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(bspec, P(None, None), espec, espec, espec),
                 out_specs=(bspec, P()), check_vma=False)
        def _sharded(xl, wg, w1, w3, w2):
            y, aux = _moe_shard_body(cfg, axes.model, xl, wg, w1, w3, w2,
                                     all_axes)
            return y, aux

        y, aux = _sharded(x, p["wg"], p["w1"], p["w3"], p["w2"])
    if cfg.num_shared_experts > 0:
        y = y + layers.mlp_fwd(cfg, p["shared"], x)
    return y, aux


def _moe_shard_body(cfg: ModelConfig, model_axis, xl, wg, w1, w3, w2,
                    all_axes):
    """Per-device body: local dispatch to this shard's experts, psum combine."""
    B, S, D = xl.shape
    T = B * S
    E = cfg.num_experts
    tp = jax.lax.axis_size(model_axis)
    El = E // tp
    m = jax.lax.axis_index(model_axis)
    xt = xl.reshape(T, D)

    vals, ids, aux = _route(cfg, wg, xt)              # (T,k)
    C = expert_capacity(cfg, T)

    # slot within expert via one-hot cumsum over flattened choices
    flat_ids = ids.reshape(-1)                        # (T*k,)
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    slots = (jnp.cumsum(oh, axis=0) - 1) * oh         # rank among same-expert
    flat_slots = jnp.sum(slots, axis=-1)              # (T*k,)

    local = (flat_ids >= m * El) & (flat_ids < (m + 1) * El) & (flat_slots < C)
    e_local = jnp.where(local, flat_ids - m * El, El)   # El = out-of-bounds drop
    s_local = jnp.where(local, flat_slots, C)

    token_idx = jnp.repeat(jnp.arange(T), cfg.experts_per_token)
    buf = jnp.zeros((El, C, D), xl.dtype)
    buf = buf.at[e_local, s_local].set(xt[token_idx], mode="drop")

    y = _expert_mlp(cfg, {"w1": w1, "w3": w3, "w2": w2}, buf)   # (El, C, D)

    gathered = y.at[e_local, s_local].get(mode="fill", fill_value=0.0)  # (T*k, D)
    w = jnp.where(local, vals.reshape(-1), 0.0).astype(xl.dtype)
    out = jnp.zeros((T, D), xl.dtype).at[token_idx].add(gathered * w[:, None])
    out = jax.lax.psum(out, model_axis)
    aux = jax.lax.pmean(aux, all_axes)
    return out.reshape(B, S, D), aux


def _moe_local(cfg: ModelConfig, p, x):
    """Single-device fallback (no model axis): dense loop over experts."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    vals, ids, aux = _route(cfg, p["wg"], xt)
    T = xt.shape[0]
    C = expert_capacity(cfg, T)
    E = cfg.num_experts
    flat_ids = ids.reshape(-1)
    oh = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
    slots = (jnp.cumsum(oh, axis=0) - 1) * oh
    flat_slots = jnp.sum(slots, axis=-1)
    ok = flat_slots < C
    e_idx = jnp.where(ok, flat_ids, E)
    s_idx = jnp.where(ok, flat_slots, C)
    token_idx = jnp.repeat(jnp.arange(T), cfg.experts_per_token)
    buf = jnp.zeros((E, C, D), x.dtype).at[e_idx, s_idx].set(xt[token_idx], mode="drop")
    y = _expert_mlp(cfg, p, buf)
    gathered = y.at[e_idx, s_idx].get(mode="fill", fill_value=0.0)
    w = jnp.where(ok, vals.reshape(-1), 0.0).astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[token_idx].add(gathered * w[:, None])
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Reference (oracle) MoE: exact dense computation, no capacity drops.
# Used by tests to bound the capacity path's deviation and by kernels/ref.
# ---------------------------------------------------------------------------


def moe_ref(cfg: ModelConfig, p, x):
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    vals, ids, _ = _route(cfg, p["wg"], xt)
    outs = []
    for e in range(cfg.num_experts):
        act = jax.nn.silu if cfg.act == "silu" else (lambda u: jax.nn.gelu(u, approximate=True))
        g = act(xt @ p["w1"][e])
        u = xt @ p["w3"][e]
        outs.append((g * u) @ p["w2"][e])
    ys = jnp.stack(outs, 0)                              # (E, T, D)
    w_full = jnp.zeros((xt.shape[0], cfg.num_experts), jnp.float32)
    w_full = w_full.at[jnp.arange(xt.shape[0])[:, None], ids].set(vals)
    out = jnp.einsum("te,etd->td", w_full, ys.astype(jnp.float32))
    y = out.reshape(B, S, D).astype(x.dtype)
    if cfg.num_shared_experts > 0:
        y = y + layers.mlp_fwd(cfg, p["shared"], x)
    return y
