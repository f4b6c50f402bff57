"""Sharding regimes for the production mesh.

Two regimes (DESIGN.md §4):

* ``tp`` (train / prefill): batch over DP axes; attention q-heads, FFN
  columns, experts, SSM/LRU channels over ``model``.  Archs whose head count
  does not divide the model axis (qwen2:14, smollm:15, whisper:8, rg:10)
  fall back to sequence-parallel attention (q positions sharded over
  ``model``, kv replicated) — the residual stream stays replicated over
  ``model`` either way.
* ``decode`` (serve): batch over DP axes; KV-cache *sequence* dim over
  ``model`` (flash-decoding partial softmax); experts over ``model``;
  attention projection weights replicated (q-heads unsharded).

All rules are name-based over the param pytree; divisibility is checked
against the concrete axis size and falls back to replication (recorded by
`explain()` for the roofline notes).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.api import MeshAxes, ModelConfig
from repro.models import transformer as T

STACKS = ("layers", "units", "tail", "enc_layers")


def _names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        else:
            out.append(str(getattr(k, "idx", k)))
    return tuple(out)


def _div(n, tp):
    return tp > 0 and n % tp == 0


def param_specs(cfg: ModelConfig, axes: MeshAxes, tp: int, regime: str,
                n_dev: int = 0):
    """PartitionSpec pytree matching init_params(cfg).

    regimes: 'tp' (train/prefill TP+EP), 'decode' (DP+EP+SP),
    'fsdp' (ZeRO-3: every weight sharded over ALL axes on its largest
    divisible dim; XLA inserts per-layer all-gathers + grad
    reduce-scatters — the beyond-paper winner for small-model training,
    see EXPERIMENTS.md §Perf)."""
    M = axes.model
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    all_ax = axes.batch + ((M,) if M else ())

    def rule(path, leaf):
        names = _names(path)
        stacked = names[0] in STACKS
        shape = leaf.shape[1:] if stacked else leaf.shape
        if regime == "fsdp":
            sp = _fsdp_rule(shape, n_dev, all_ax, tp, M)
        else:
            sp = _leaf_rule(cfg, names, shape, tp, M, regime)
        if stacked:
            sp = P(None, *sp)
        return sp

    return jax.tree_util.tree_map_with_path(rule, shapes)


def _fsdp_rule(shape, n_dev, all_ax, tp, M):
    """Shard the largest dim divisible by the full device count; fall back
    to a partial shard over the last mesh axis; else replicate."""
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if n_dev and shape[i] % n_dev == 0:
            return P(*[all_ax if j == i else None for j in range(len(shape))])
    last = all_ax[-1] if all_ax else M
    for i in order:
        if shape[i] % tp == 0:
            return P(*[last if j == i else None for j in range(len(shape))])
    return P(*([None] * len(shape)))


def _leaf_rule(cfg, names, shape, tp, M, regime):
    last = names[-1]
    in_moe = "moe" in names and "shared" not in names
    is_attn = any(n in ("attn", "xattn") for n in names) or (
        "t" in names and last in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"))
    attn_repl = regime == "decode"  # decode: q-heads unsharded, cache sharded

    if last == "embed":
        return P(M, None) if _div(T.padded_vocab(cfg), tp) else P(None, None)
    if last == "lm_head":
        return P(None, M) if _div(T.padded_vocab(cfg), tp) else P(None, None)
    if last == "adapter":
        return P(None, None)

    # --- attention ---
    if is_attn or last in ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
                           "q_norm", "kv_norm"):
        if attn_repl:
            return P(*([None] * len(shape)))
        if last == "wq":
            return P(None, M, None) if _div(cfg.num_heads, tp) else P(None, None, None)
        if last in ("wk", "wv"):
            return P(None, M, None) if _div(cfg.num_kv_heads, tp) else P(None, None, None)
        if last == "wo":
            return P(M, None, None) if _div(cfg.num_heads, tp) else P(None, None, None)
        if last == "bq":
            return P(M, None) if _div(cfg.num_heads, tp) else P(None, None)
        if last in ("bk", "bv"):
            return P(M, None) if _div(cfg.num_kv_heads, tp) else P(None, None)
        if last in ("wq_b", "wk_b", "wv_b"):
            return P(None, M, None) if _div(cfg.num_heads, tp) else P(None, None, None)
        if last in ("wq_a", "wkv_a"):
            return P(None, None)
        if last in ("q_norm", "kv_norm"):
            return P(None)

    # --- MoE experts ---
    if in_moe:
        if last == "wg":
            return P(None, None)
        if last in ("w1", "w2", "w3") and len(shape) == 3:
            return (P(M, None, None) if _div(cfg.num_experts, tp)
                    else P(None, None, None))
        # shared-expert fallthrough handled below (dense rules)

    # --- dense MLP ---
    if last in ("w1", "w3"):
        F = shape[-1]
        return P(None, M) if _div(F, tp) else P(None, None)
    if last == "w2":
        F = shape[0]
        return P(M, None) if _div(F, tp) else P(None, None)

    # --- SSM (mamba2) ---
    if last in ("wz", "wx", "conv_x"):
        return P(None, M) if _div(cfg.d_inner if cfg.family == "ssm" else cfg.lru_width, tp) else P(None, None)
    if last in ("wB", "wC", "conv_B", "conv_C"):
        return P(None, None)
    if last == "wdt":
        return P(None, M) if _div(cfg.ssm_heads, tp) else P(None, None)
    if last in ("dt_bias", "A_log", "D_skip"):
        return P(M) if _div(cfg.ssm_heads, tp) else P(None)
    if last == "norm_w":
        return P(M) if _div(cfg.d_inner, tp) else P(None)
    if last == "wout":
        W = shape[0]
        return P(M, None) if _div(W, tp) else P(None, None)

    # --- RG-LRU ---
    if last in ("wgate",):
        return P(None, M) if _div(cfg.lru_width, tp) else P(None, None)
    if last == "conv":
        return P(None, M) if _div(cfg.lru_width, tp) else P(None, None)
    if last in ("Wa", "Wi"):
        return P(M, None, None) if _div(shape[0], tp) else P(None, None, None)
    if last in ("ba", "bi"):
        return P(M, None) if _div(shape[0], tp) else P(None, None)
    if last == "lam":
        return P(M) if _div(cfg.lru_width, tp) else P(None)

    # norms / biases / everything else: replicated
    return P(*([None] * len(shape)))


# ---------------------------------------------------------------------------
# cache specs (decode regime)
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, axes: MeshAxes, tp: int, batch: int,
                mesh_batch: int):
    """Spec pytree matching init_cache(cfg, B, S).

    Sequence dims shard over ``model`` (flash-decoding); batch over DP axes
    when divisible (long_500k batch=1 replicates).
    """
    M = axes.model
    Bax = axes.batch if batch % max(mesh_batch, 1) == 0 else None
    shapes = jax.eval_shape(lambda: T.init_cache(cfg, batch, 1024))

    def rule(path, leaf):
        names = _names(path)
        last = names[-1]
        nd = leaf.ndim
        # all caches are stacked: leading L/U dim
        if last in ("k", "v", "xk", "xv"):        # (L,B,S,Hkv,dh)
            return P(None, Bax, M, None, None)
        if last in ("ckv", "kr"):                 # (L,B,S,R)
            return P(None, Bax, M, None)
        if last == "pos":                         # (L,B,Wc)
            return P(None, Bax, M)
        if last == "state" and nd == 5:           # ssm (L,B,H,N,P)
            return P(None, Bax, M if _div(cfg.ssm_heads, tp) else None,
                     None, None)
        if last == "state":                       # rg (L,B,W)
            return P(None, Bax, M if _div(cfg.lru_width, tp) else None)
        if last in ("conv_x",):                   # (L,B,K-1,W)
            return P(None, Bax, None, M if _div(cfg.d_inner, tp) else None)
        if last in ("conv_B", "conv_C"):
            return P(None, Bax, None, None)
        if last == "conv":                        # rg (L,B,K-1,W)
            return P(None, Bax, None, M if _div(cfg.lru_width, tp) else None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, shapes)


# ---------------------------------------------------------------------------
# batch / activation hints
# ---------------------------------------------------------------------------


def batch_specs(cfg: ModelConfig, axes: MeshAxes, batch: int, mesh_batch: int,
                kind: str):
    Bax = axes.batch if batch % max(mesh_batch, 1) == 0 else None
    sp: Dict[str, Any] = {"tokens": P(Bax, None)}
    if kind == "train":
        sp["labels"] = P(Bax, None)
    if cfg.family == "vlm" and kind in ("train", "prefill"):
        sp["patches"] = P(Bax, None, None)
    if cfg.family == "audio" and kind in ("train", "prefill"):
        sp["frames"] = P(Bax, None, None)
    if kind == "decode":
        sp = {"tokens": P(Bax), "lengths": P(Bax)}
    return sp


def attention_mode(cfg: ModelConfig, tp: int) -> str:
    """'heads' TP when divisible, else sequence-parallel 'seq'."""
    if cfg.num_heads and cfg.num_heads % max(tp, 1) == 0:
        return "heads"
    return "seq"


def make_hint(cfg: ModelConfig, axes: MeshAxes, tp: int):
    """Sharding hint applied to (q, k, v) inside attention (tp regime)."""
    mode = attention_mode(cfg, tp)
    M = axes.model

    def hint(q, k, v):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or M not in mesh.axis_names:
            return q, k, v
        wsc = jax.lax.with_sharding_constraint
        if mode == "heads":
            q = wsc(q, P(axes.batch, None, M, None))
            kv_sp = (P(axes.batch, None, M, None)
                     if cfg.num_kv_heads % max(tp, 1) == 0
                     else P(axes.batch, None, None, None))
            k, v = wsc(k, kv_sp), wsc(v, kv_sp)
        else:
            q = wsc(q, P(axes.batch, M, None, None))
            k = wsc(k, P(axes.batch, None, None, None))
            v = wsc(v, P(axes.batch, None, None, None))
        return q, k, v

    return hint


def explain(cfg: ModelConfig, tp: int) -> str:
    mode = attention_mode(cfg, tp)
    notes = [f"attention={mode}"]
    if cfg.is_moe:
        notes.append(f"EP {cfg.num_experts}/{tp} experts per shard")
    if cfg.family in ("ssm", "hybrid"):
        notes.append("channel TP")
    return ", ".join(notes)
