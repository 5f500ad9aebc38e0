"""End-to-end training driver.

Runs REAL steps on the local devices (CPU here, TPU in deployment) with the
full substrate: synthetic pipeline, AdamW, checkpoint/restart, and the
paper's partition runtime when --partitions > 1.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
      --steps 40 --partitions 4 --sync-every 8
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --steps 20 --resume
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import SMOKE_SHAPES, SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.core.partitioning import PartitionConfig
from repro.data.pipeline import synth_lm_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as mapi
from repro.optim.adamw import adamw_init
from repro.runtime import steps as RS
from repro.runtime.partition_runtime import PartitionRuntime


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--partitions", type=int, default=1)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoints per N sync points")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", default="",
                    help="step:partition failure injection, e.g. 12:1")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    base = SMOKE_SHAPES["train_4k"] if args.smoke else SHAPES["train_4k"]
    shape = ShapeCell("train", args.seq or base.seq_len,
                      args.batch or base.global_batch, "train")
    api = mapi.build(cfg)
    pc = PartitionConfig(partitions=args.partitions,
                         sync_every=args.sync_every)
    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name)

    print(f"train: {cfg.name} seq={shape.seq_len} batch={shape.global_batch} "
          f"P={pc.partitions} W={pc.sync_every} devices={jax.device_count()}")

    step_fn = RS.make_train_step(api, peak_lr=args.lr, accum=args.accum,
                                 total=max(args.steps, 100))

    if pc.partitions > 1:
        rt = PartitionRuntime(api, step_fn, pc, jax.random.PRNGKey(0))

        def make_batches(step):
            b = synth_lm_batch(cfg, shape, step, partitions=pc.partitions)
            return [{k: v[i] for k, v in b.items()}
                    for i in range(pc.partitions)]

        fail = {}
        if args.fail_at:
            s, p = args.fail_at.split(":")
            fail = {int(s): int(p)}
        t0 = time.time()
        losses = rt.train(make_batches, args.steps, ckpt=ckpt,
                          ckpt_every=args.ckpt_every, fail_at=fail)
        dt = time.time() - t0
        first = np.mean(list(losses[0].values()))
        last = np.mean(list(losses[-1].values()))
        print(f"P={pc.partitions}: loss {first:.4f} -> {last:.4f} "
              f"({args.steps} steps, {dt:.1f}s, {rt.sync_count} syncs)")
        return losses

    # single-partition (synchronous) path with resume
    params = api.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        tmpl = {"params": params, "opt": opt._asdict()}
        state, meta = ckpt.restore(tmpl)
        params = state["params"]
        opt = opt._replace(**{k: state["opt"][k] for k in ("step", "m", "v")})
        start = int(meta["step"])
        print(f"resumed from step {start}")

    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    t0 = time.time()
    losses = []
    for step in range(start, start + args.steps):
        batch = synth_lm_batch(cfg, shape, step)
        params, opt, m = jstep(params, opt, batch)
        losses.append(float(m["loss"]))
        if (step + 1) % 10 == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt._asdict()})
            print(f"step {step+1}: loss={losses[-1]:.4f} "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)")
    ckpt.save(start + args.steps, {"params": params, "opt": opt._asdict()})
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
