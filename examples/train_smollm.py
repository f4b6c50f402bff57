"""Train a ~100M-class model (SmolLM-360M family, width-reduced to train on a
CPU) for a few hundred steps with the production train_step:
microbatched grad accumulation + ZeRO-1 AdamW + remat + flash attention.

    PYTHONPATH=src python examples/train_smollm.py [--steps 200]
"""
import argparse
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import get_config
from repro.models import transformer as T
from repro.models.api import MeshAxes

AXES = MeshAxes()


def synthetic_lm_batch(rng, B, S, vocab):
    """Markov-chain synthetic data so the loss has learnable structure."""
    trans = rng.integers(2, vocab, (vocab,))
    toks = np.zeros((B, S), np.int32)
    toks[:, 0] = rng.integers(2, vocab, B)
    for t in range(1, S):
        toks[:, t] = np.where(rng.random(B) < 0.8, trans[toks[:, t - 1]],
                              rng.integers(2, vocab, B))
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = dataclasses.replace(get_config("smollm_360m"), num_layers=6,
                              d_model=256, num_heads=8, num_kv_heads=4,
                              head_dim=32, d_ff=512, vocab_size=1024)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n/1e6:.1f}M params")
    ocfg = optim.AdamWConfig(lr=1e-3, zero1=False, weight_decay=0.01)
    opt = optim.init_opt_state(params, n_dev=1)

    @jax.jit
    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.forward_loss(cfg, AXES, p, batch, remat=True))(params)
        params, opt, gnorm = optim.apply_updates(ocfg, params, grads, opt, 1)
        return params, opt, loss, gnorm

    rng = np.random.default_rng(0)
    t0 = time.time()
    for step in range(args.steps):
        batch = synthetic_lm_batch(rng, args.batch, args.seq, cfg.vocab_size)
        params, opt, loss, gnorm = train_step(params, opt, batch)
        if step % 20 == 0 or step == args.steps - 1:
            toks = args.batch * args.seq * (step + 1)
            print(f"step {step:4d} loss={float(loss):7.4f} "
                  f"gnorm={float(gnorm):6.2f} "
                  f"tok/s={toks/(time.time()-t0):8.0f}")
    print("done — loss should have dropped well below ln(vocab)=6.9")


if __name__ == "__main__":
    main()
