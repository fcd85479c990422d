"""Training driver: bubble-planned sharded train loop with fault tolerance.

Runs on any mesh (1x1 on a CPU; 2x2 on a four-chip v5e host; 16x16 /
2x16x16 in production — same code path).  Features:

* bubble-planner-derived shardings (``--strategy bubbles|simple|bound``)
* AdamW with fp32 master + bf16 moments, ZeRO-1 over ``data``
* block-granularity remat, donated buffers
* checkpoint/restart (atomic, manifest-based; ``--resume`` picks up the
  latest step, including onto a *different* mesh — elastic restart)
* straggler detector fed with per-step wall times
* optional int8 error-feedback gradient compression for the cross-pod hop

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 10 --batch 4 --seq 64
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCHS, get_config
from repro.core.planner import MeshAxis, plan_bubbles, plan_simple
from repro.data import DataConfig, PrefetchBuffer, ShardedTokenStream
from repro.distributed import sharding as shard_mod
from repro.distributed.fault_tolerance import StragglerDetector
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, mesh_axes
from repro.models import api
from repro.optim import adamw


def build_train_step(cfg, acfg, use_compression: bool = False):
    loss_fn = api.make_loss_fn(cfg, remat=True)
    pdtype = cfg.pdtype

    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if use_compression:
            from repro.optim import compression
            # int8 quantise-dequantise on the gradient path (the cross-pod
            # all-reduce then moves int8 bytes; EF residual is carried in
            # the opt state extra slot in the full deployment)
            qs = jax.tree.map(lambda g: compression.quantize(g), grads,
                              is_leaf=lambda x: hasattr(x, "dtype"))
            grads = jax.tree.map(lambda t: compression.dequantize(*t), qs,
                                 is_leaf=lambda t: isinstance(t, tuple))
        new_params, new_opt = adamw.apply(grads, opt, acfg,
                                          param_dtype=pdtype)
        return loss, new_params, new_opt

    return train_step


def run(argv=None) -> dict:
    """Parse ``argv``, train, and return ``{"losses": [...],
    "state_bytes": {device: bytes}, "state_total": bytes}`` — the
    per-step losses, the parameter + optimizer bytes each device holds
    after placement, and the bytes of one whole copy of that state."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=ARCHS)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths kept)")
    ap.add_argument("--strategy", default="bubbles",
                    choices=["bubbles", "simple"])
    ap.add_argument("--mesh", default="1x1",
                    help="e.g. 1x1, 2x4, 2x16x16 (axes inferred)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint here every --ckpt-every steps and at "
                         "the end (default: no checkpoints)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and args.ckpt_dir is None:
        ap.error("--resume needs --ckpt-dir")

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)

    shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(shape)]
    mesh = make_mesh(shape, axes)
    maxes = [MeshAxis(n, s) for n, s in mesh_axes(mesh)]

    # plan via the bubble scheduler (or the opportunist baseline)
    tree = api.bubble_tree(cfg, "train_4k")
    # patch the batch width to the actual run batch
    for d in tree.children[0].children:
        d.width = args.batch
    plan = (plan_bubbles(tree, maxes) if args.strategy == "bubbles"
            else plan_simple("batch", maxes))
    print(plan.pretty())

    def named(specs):
        return jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs)

    with mesh:
        p_sh = named(shard_mod.param_specs(cfg, plan, mesh))
        o_sh = named(shard_mod.opt_specs(cfg, plan, mesh))

        # every leaf is made on the devices its sharding names: nothing
        # is built whole on the default device first
        params = api.init(cfg, jax.random.PRNGKey(args.seed), p_sh)
        acfg = adamw.AdamWConfig(lr=args.lr)
        opt = jax.jit(adamw.init, out_shardings=o_sh)(params)

        start = 0
        if args.resume:
            latest = ckpt.latest_step(args.ckpt_dir)
            if latest is not None:
                params, _ = ckpt.restore(args.ckpt_dir, latest, params,
                                         shardings=p_sh)
                opt, _ = ckpt.restore(Path(args.ckpt_dir) / "opt", latest,
                                      opt, shardings=o_sh)
                start = latest
                print(f"resumed from step {latest}")

        state_bytes: dict = {}
        for leaf in jax.tree.leaves((params, opt)):
            for sh in leaf.addressable_shards:
                d = str(sh.device)
                state_bytes[d] = state_bytes.get(d, 0) + sh.data.nbytes
        state_total = sum(leaf.nbytes
                          for leaf in jax.tree.leaves((params, opt)))
        print(f"params+opt bytes per device: {state_bytes} "
              f"(one whole copy: {state_total})")

        data = ShardedTokenStream(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
            seed=args.seed))
        it = PrefetchBuffer(data.shard(0, 0))
        b_sh = named(shard_mod.batch_specs(
            cfg, plan, api.train_specs(cfg, args.batch, args.seq)))

        step_fn = jax.jit(
            build_train_step(cfg, acfg, args.compress_grads),
            donate_argnums=(0, 1))
        detector = StragglerDetector()

        host = "host0"
        losses = []
        for step in range(start, args.steps):
            batch = jax.device_put(next(it), b_sh)
            t0 = time.time()
            loss, params, opt = step_fn(params, opt, batch)
            loss = float(loss)
            dt = time.time() - t0
            detector.observe(host, dt)
            print(f"step {step:5d} loss {loss:8.4f} {dt*1e3:7.1f}ms")
            assert np.isfinite(loss), "loss diverged"
            losses.append(loss)
            if args.ckpt_dir is not None and (
                    (step + 1) % args.ckpt_every == 0
                    or step + 1 == args.steps):
                ckpt.save(args.ckpt_dir, step + 1, params,
                          extra={"arch": cfg.name, "loss": loss})
                ckpt.save(Path(args.ckpt_dir) / "opt", step + 1, opt)
        stragglers = detector.stragglers()
        if stragglers:
            print(f"stragglers detected: {stragglers}")
    print("done")
    return {"losses": losses, "state_bytes": state_bytes,
            "state_total": state_total}


def main(argv=None):
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
