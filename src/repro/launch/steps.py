"""Step builders: train_step / prefill_step / serve_step per (arch x shape).

``build_cell`` returns everything the dry-run, roofline harness and the
runtime engine need: the step function, sharded ShapeDtypeStruct inputs,
in/out shardings and donation indices.  No device memory is allocated —
inputs are abstract until a caller materializes them.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.models.api import SHAPES, MeshAxes, ModelConfig, shape_applicable


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    cfg: ModelConfig
    mesh: Any
    axes: MeshAxes
    step: Callable
    in_sds: tuple              # ShapeDtypeStructs (sharded)
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple
    kind: str
    note: str = ""

    def jitted(self):
        return jax.jit(self.step, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        with jax.set_mesh(self.mesh):
            return self.jitted().lower(*self.in_sds)


def _ns(mesh, tree):
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _train_seq_hint(cfg, axes, tp):
    return shd.make_hint(cfg, axes, tp)


def build_cell(arch: str, shape_name: str, mesh, *, unroll: bool = False,
               opt_cfg: Optional[optim.AdamWConfig] = None,
               microbatches: int = 4,
               exact_microbatches: Optional[int] = None,
               train_regime: str = "tp") -> Cell:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"{arch} x {shape_name}: {why}")
    axes = mesh_lib.mesh_axes(mesh)
    tp = mesh.shape["model"]
    mesh_batch = mesh_lib.batch_extent(mesh)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        n_mb = (exact_microbatches if exact_microbatches
                else _auto_microbatches(cfg, B, S, mesh_batch, microbatches))
        return _build_train(cfg, arch, shape_name, mesh, axes, tp, mesh_batch,
                            B, S, unroll, opt_cfg or optim.AdamWConfig(),
                            n_mb, train_regime)
    if shape.kind == "prefill":
        return _build_prefill(cfg, arch, shape_name, mesh, axes, tp,
                              mesh_batch, B, S, unroll)
    return _build_decode(cfg, arch, shape_name, mesh, axes, tp, mesh_batch,
                         B, S, unroll)


# ---------------------------------------------------------------------------


def _auto_microbatches(cfg, B, S, mesh_batch, floor, target=2 * 2**30):
    """Pick the microbatch count so the per-device remat stash (one hidden
    state per layer per microbatch) stays under `target` bytes."""
    L = cfg.num_layers + cfg.encoder_layers
    n = 1
    while n < floor and B % (2 * n * mesh_batch) == 0:
        n *= 2
    per_layer = lambda nn: (B // mesh_batch // nn) * S * cfg.d_model * 2
    while (L * per_layer(n) > target and B % (2 * n * mesh_batch) == 0
           and B // mesh_batch // n > 1):
        n *= 2
    return n


def _batch_sds(cfg, kind, B, S, bspecs, mesh):
    out = {}
    if kind == "decode":
        out["tokens"] = jax.ShapeDtypeStruct(
            (B,), jnp.int32, sharding=NamedSharding(mesh, bspecs["tokens"]))
        out["lengths"] = jax.ShapeDtypeStruct(
            (B,), jnp.int32, sharding=NamedSharding(mesh, bspecs["lengths"]))
        return out
    S_text = S
    if cfg.family == "vlm":
        S_text = S - cfg.num_patches
        out["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, bspecs["patches"]))
    if cfg.family == "audio":
        out["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, bspecs["frames"]))
    out["tokens"] = jax.ShapeDtypeStruct(
        (B, S_text if cfg.family == "vlm" else S), jnp.int32,
        sharding=NamedSharding(mesh, bspecs["tokens"]))
    if kind == "train":
        out["labels"] = jax.ShapeDtypeStruct(
            (B, S), jnp.int32, sharding=NamedSharding(mesh, bspecs["labels"]))
    return out


def _build_train(cfg, arch, shape_name, mesh, axes, tp, mesh_batch, B, S,
                 unroll, ocfg, n_mb=1, regime="tp"):
    if regime == "fsdp":
        # ZeRO-3: the whole mesh is the DP world; weights gather per layer
        axes = MeshAxes(batch=axes.batch + (axes.model,), model=None)
        mesh_batch = mesh.size
        n_mb = 1
    pspecs = shd.param_specs(cfg, axes, tp, regime, n_dev=mesh.size)
    bspecs = shd.batch_specs(cfg, axes, B, mesh_batch, "train")
    hint = shd.make_hint(cfg, axes, tp) if regime == "tp" else None
    n_dev = mesh.size
    flat_spec = P(tuple(a for a in (("pod",) if "pod" in mesh.axis_names else ())
                        + ("data", "model"))) if ocfg.zero1 else P()
    flat_sharding = NamedSharding(mesh, flat_spec)
    param_shardings = _ns(mesh, pspecs)

    def loss_fn(params, batch):
        return T.forward_loss(cfg, axes, params, batch, hint=hint, remat=True,
                              unroll=unroll)

    def _mb_split(x):
        # (B, ...) -> (n_mb, B/n_mb, ...), keeping the DP shard on dim 1
        y = x.reshape(n_mb, x.shape[0] // n_mb, *x.shape[1:])
        sp = P(None, axes.batch, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(y, sp)

    def train_step(state, batch):
        params, opt_state = state["params"], state["opt"]

        if n_mb > 1:
            # microbatch gradient accumulation (Alg. 1 sub-batching applied
            # at the training level): bounds the remat stash to one
            # microbatch; grads accumulate in fp32.
            mbs = jax.tree.map(_mb_split, batch)

            def mb_step(carry, mb):
                loss_sum, gacc = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (loss_sum + l, gacc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                mb_step, (jnp.zeros((), jnp.float32), g0), mbs,
                unroll=unroll)
            loss = loss / n_mb
            grads = jax.tree.map(lambda g: g / n_mb, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        new_params, new_opt, gnorm = optim.apply_updates(
            ocfg, params, grads, opt_state, n_dev,
            flat_sharding=flat_sharding, param_shardings=param_shardings)
        return ({"params": new_params, "opt": new_opt},
                {"loss": loss, "grad_norm": gnorm})

    params_sds = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params_sds = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        params_sds, pspecs)
    opt_sds = jax.eval_shape(partial(optim.init_opt_state, n_dev=n_dev),
                             params_sds)
    opt_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=flat_sharding if l.ndim == 1
                                       else NamedSharding(mesh, P())),
        opt_sds)
    state_sds = {"params": params_sds, "opt": opt_sds}
    batch_sds = _batch_sds(cfg, "train", B, S, bspecs, mesh)

    state_sh = jax.tree.map(lambda l: l.sharding, state_sds)
    batch_sh = jax.tree.map(lambda l: l.sharding, batch_sds)
    metrics_sh = {"loss": NamedSharding(mesh, P()),
                  "grad_norm": NamedSharding(mesh, P())}
    return Cell(arch, shape_name, cfg, mesh, axes, train_step,
                (state_sds, batch_sds), (state_sh, batch_sh),
                (state_sh, metrics_sh), (0,), "train",
                note=shd.explain(cfg, tp))


def _build_prefill(cfg, arch, shape_name, mesh, axes, tp, mesh_batch, B, S,
                   unroll):
    pspecs = shd.param_specs(cfg, axes, tp, "tp")
    bspecs = shd.batch_specs(cfg, axes, B, mesh_batch, "prefill")
    cspecs = shd.cache_specs(cfg, axes, tp, B, mesh_batch)
    hint = shd.make_hint(cfg, axes, tp)

    def prefill_step(params, batch):
        logits, cache = T.prefill(cfg, axes, params, batch, hint=hint,
                                  unroll=unroll)
        return logits, cache

    params_sds = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params_sds = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        params_sds, pspecs)
    batch_sds = _batch_sds(cfg, "prefill", B, S, bspecs, mesh)

    Bax = bspecs["tokens"][0]
    out_sh = (NamedSharding(mesh, P(Bax, None, None)), _ns(mesh, _prefill_cache_specs(cfg, axes, tp, B, mesh_batch, S)))
    return Cell(arch, shape_name, cfg, mesh, axes, prefill_step,
                (params_sds, batch_sds),
                (jax.tree.map(lambda l: l.sharding, params_sds),
                 jax.tree.map(lambda l: l.sharding, batch_sds)),
                out_sh, (), "prefill", note=shd.explain(cfg, tp))


def _prefill_cache_specs(cfg, axes, tp, B, mesh_batch, S):
    """Prefill emits the decode-layout cache (seq over model)."""
    return shd.cache_specs(cfg, axes, tp, B, mesh_batch)


def _build_decode(cfg, arch, shape_name, mesh, axes, tp, mesh_batch, B, S,
                  unroll):
    pspecs = shd.param_specs(cfg, axes, tp, "decode")
    bspecs = shd.batch_specs(cfg, axes, B, mesh_batch, "decode")
    cspecs = shd.cache_specs(cfg, axes, tp, B, mesh_batch)

    def serve_step(params, cache, tokens, lengths):
        next_tokens, new_cache = T.decode_step(cfg, axes, params, cache,
                                               tokens, lengths, unroll=unroll)
        return next_tokens, new_cache, lengths + 1

    params_sds = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params_sds = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        params_sds, pspecs)
    cache_sds = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    cache_sds = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        cache_sds, cspecs)
    tok_sds = _batch_sds(cfg, "decode", B, S, bspecs, mesh)

    Bax = bspecs["tokens"][0]
    out_sh = (NamedSharding(mesh, bspecs["tokens"]),
              jax.tree.map(lambda l: l.sharding, cache_sds),
              NamedSharding(mesh, bspecs["lengths"]))
    return Cell(arch, shape_name, cfg, mesh, axes, serve_step,
                (params_sds, cache_sds, tok_sds["tokens"], tok_sds["lengths"]),
                (jax.tree.map(lambda l: l.sharding, params_sds),
                 jax.tree.map(lambda l: l.sharding, cache_sds),
                 tok_sds["tokens"].sharding, tok_sds["lengths"].sharding),
                out_sh, (1,), "decode", note=shd.explain(cfg, tp))
