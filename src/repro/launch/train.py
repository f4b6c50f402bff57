"""Production training driver: builds the train cell for an (arch) on the
production mesh and — on real hardware — runs the step loop with
checkpoint/restart.  --dry lowers + compiles only on 512 virtual CPU
devices (see dryrun.py for the full matrix); --reduced actually trains a
few steps.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b --dry
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--dry", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.dry:
        import os

        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=512")
        from repro.launch import steps
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cell = steps.build_cell(args.arch, "train_4k", mesh)
        compiled = cell.lower().compile()
        ma = compiled.memory_analysis()
        print(f"{args.arch} train_4k compiled for {dict(mesh.shape)}; "
              f"peak/device={ (ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes)/2**30:.2f} GiB")
        return

    # reduced real training on CPU
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import optim
    from repro.configs import reduced_config
    from repro.models import transformer as T
    from repro.models.api import MeshAxes
    from repro.runtime import checkpoint as ckpt

    cfg = reduced_config(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = optim.AdamWConfig(lr=1e-3, zero1=False)
    opt = optim.init_opt_state(params, n_dev=1)
    rng = np.random.default_rng(0)

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.forward_loss(cfg, MeshAxes(), p, batch,
                                     remat=True))(params)
        params, opt, _ = optim.apply_updates(ocfg, params, grads, opt, 1)
        return params, opt, loss

    for i in range(args.steps):
        toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (4, 32)), jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        params, opt, loss = step(params, opt, batch)
        if i % 5 == 0:
            print(f"step {i} loss {float(loss):.4f}")
    ckpt.save("/tmp/repro_train_ckpt", params, extra={"steps": args.steps})
    print("checkpoint saved to /tmp/repro_train_ckpt")


if __name__ == "__main__":
    main()
