"""Production training launcher: mesh + partition rules + pjit'd two-phase
SONIQ training.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --reduced --steps 20 --mesh 1x1          # CPU smoke
    python -m repro.launch.train --arch deepseek-67b --mesh 16x16 ...  # TPU

On a real cluster each host runs this under jax.distributed; here the mesh
degenerates gracefully to whatever devices exist. The dry-run
(repro.launch.dryrun) is the no-allocation version of exactly this wiring.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import soniq
from repro.backend import registry as backend_registry
from repro.configs import get_config
from repro.data import synthetic
from repro.launch import mesh as mesh_lib
from repro.launch import runtime
from repro.launch import sharding as sh
from repro.models import shard as shard_ctx
from repro.optim import adamw
from repro.train import checkpoint as ckpt_lib
from repro.train import state as state_lib


def parse_mesh(s: str):
    dims = [int(x) for x in s.split("x")]
    if len(dims) == 1:
        return mesh_lib.make_mesh((dims[0],), ("data",))
    if len(dims) == 2:
        return mesh_lib.make_mesh(tuple(dims), ("data", "model"))
    return mesh_lib.make_mesh(tuple(dims), ("pod", "data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--t1", type=int, default=0, help="Phase I steps")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--hoist", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args()

    runtime.enable_compile_cache()
    print(f"device: {runtime.device_summary()}")
    # The kernel backend comes from the environment (no flag here).
    if os.environ.get(backend_registry.ENV_VAR, "").strip() \
            == "pallas_mosaic":
        runtime.require_tpu(f"{backend_registry.ENV_VAR}=pallas_mosaic")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = soniq.with_phase(cfg, soniq.Phase.QAT)
    mesh = parse_mesh(args.mesh)
    rules = sh.activation_rules(cfg, mesh, batch=args.batch)
    tcfg = state_lib.TrainConfig(
        num_microbatches=args.microbatches, t1=args.t1, t2=args.steps,
        warmup=max(args.steps // 10, 1), ckpt_dir=args.ckpt,
        checkpoint_every=max(args.steps // 2, 1),
        hoist_weight_quant=args.hoist, grad_compress=args.grad_compress)

    stream = synthetic.TokenStream(synthetic.TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        seed=0), host_id=jax.process_index())

    with jax.set_mesh(mesh), shard_ctx.sharding_rules(rules):
        key = jax.random.PRNGKey(0)
        state = state_lib.init_state(key, cfg, tcfg)
        state_specs = jax.eval_shape(
            lambda: state_lib.init_state(key, cfg, tcfg))
        state_sh = sh.tree_shardings(state_specs, cfg, mesh, serve=False,
                                     rules=rules)
        state = jax.device_put(state, state_sh)
        dp = rules["batch"]
        step = jax.jit(
            lambda s, b, r: state_lib.train_step(s, b, cfg, tcfg, r),
            in_shardings=(state_sh,
                          {"tokens": NamedSharding(mesh, P(dp, None)),
                           "labels": NamedSharding(mesh, P(dp, None))},
                          NamedSharding(mesh, P())),
            donate_argnums=(0,))

        start = 0
        if args.ckpt:
            latest = ckpt_lib.latest_step(args.ckpt)
            if latest is not None:
                state, start = ckpt_lib.restore(args.ckpt, state)
                print(f"resumed from step {start}")

        batches = stream.batches()
        for i in range(start, args.steps):
            b = next(batches)
            state, metrics = step(state, {k: jax.numpy.asarray(v)
                                          for k, v in b.items()},
                                  jax.random.fold_in(key, i))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i}: loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            if args.ckpt and (i + 1) % tcfg.checkpoint_every == 0:
                ckpt_lib.async_save(state, args.ckpt, i + 1).join()
    print("done")


if __name__ == "__main__":
    main()
