"""End-to-end training driver: SPTLB-routed streams -> pjit train loop with
checkpoint/restart and failure-driven rebalancing.

This is the integration point of the whole framework (DESIGN.md §2):

  1. stream apps + pod slices are assembled into the paper's tier model,
  2. SPTLB (manual_cnst co-operation) produces the app->tier routing,
  3. the local mesh trains its slice's stream partitions,
  4. failures (simulated here; device-health callbacks in production) shrink
     tier capacity, SPTLB re-balances with bounded movement, and training
     resumes from the latest checkpoint.

Runs on CPU with ``--smoke`` (reduced config); production shapes lower via
launch/dryrun.py on the 256/512-chip meshes.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 30 --global-batch 8 --seq-len 128 --inject-failure-at 12
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.fault import CapacityEvent, rebalance
from repro.launch.mesh import make_host_mesh
from repro.models import build_model, reduce_for_smoke
from repro.streams import (PodSlice, StreamConfig, StreamRouter, TokenStream,
                           build_cluster, demo_apps)
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def default_slices() -> list[PodSlice]:
    """A 5-tier cluster matching the paper's experiment setup."""
    return [
        PodSlice("tier_1", pod=0, num_hosts=64, flops_capacity=900.0,
                 hbm_capacity=2048.0, task_slots=1500, regions=(0, 1)),
        PodSlice("tier_2", pod=0, num_hosts=48, flops_capacity=700.0,
                 hbm_capacity=1536.0, task_slots=1200, regions=(1, 2)),
        PodSlice("tier_3", pod=0, num_hosts=32, flops_capacity=400.0,
                 hbm_capacity=1024.0, task_slots=800, regions=(2, 3)),
        PodSlice("tier_4", pod=1, num_hosts=48, flops_capacity=700.0,
                 hbm_capacity=1536.0, task_slots=1200, regions=(3, 4)),
        PodSlice("tier_5", pod=1, num_hosts=64, flops_capacity=900.0,
                 hbm_capacity=2048.0, task_slots=1500, regions=(4, 5)),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/run0")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a host failure at this step")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "bf16", "int8"],
                    help="compress gradients (DCN stage) w/ error feedback")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # ---- 1+2: SPTLB routing over the stream cluster -----------------------
    apps = demo_apps(48, seed=args.seed)
    cluster = build_cluster(apps, default_slices(), seed=args.seed)
    router = StreamRouter(cluster)
    decision = router.route(engine="local", variant="manual_cnst")
    print(f"[sptlb] routed {len(apps)} stream apps: moved "
          f"{decision.projected.num_moved}, d2b "
          f"{decision.difference_to_balance:.3f}, net p99 "
          f"{decision.network_p99_ms:.0f} ms, constraints ok: "
          f"{decision.violations.ok}")

    # ---- 3: local slice trains its partitions -----------------------------
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg)
    mesh = make_host_mesh(data=args.mesh_data, model=args.mesh_model)

    stream = TokenStream(StreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed))

    from repro.distributed.compress import GradCompressor
    compressor = (GradCompressor(mode=args.grad_compress)
                  if args.grad_compress != "none" else None)
    step_fn = make_train_step(model, AdamWConfig(lr=args.lr,
                                                 total_steps=args.steps),
                              compressor=compressor)
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)

    with jax.set_mesh(mesh):
        state = init_train_state(model, jax.random.PRNGKey(args.seed),
                                 compressor=compressor)
        start_step = 0
        if args.resume and ckpt.latest_step() is not None:
            state, start_step = ckpt.restore(state)
            print(f"[ckpt] resumed from step {start_step}")

        jit_step = jax.jit(step_fn, donate_argnums=(0,))

        t_last = time.perf_counter()
        for step in range(start_step, args.steps):
            if step == args.inject_failure_at:
                print(f"[fault] host failure injected at step {step}")
                event = CapacityEvent("host_failure", tier=2, fraction=0.2,
                                      step=step)
                new_cluster, dec = rebalance(cluster, event)
                router.cluster = new_cluster
                router.assignment = np.asarray(dec.assignment)
                print(f"[sptlb] rebalanced: moved {dec.projected.num_moved} "
                      f"apps, d2b {dec.difference_to_balance:.3f}, "
                      f"constraints ok: {dec.violations.ok}")
                # restart path: restore latest checkpoint (idempotent replay)
                if ckpt.latest_step() is not None:
                    state, restored = ckpt.restore(state)
                    print(f"[ckpt] restarted from step {restored}")
                    step = restored

            batch = stream.batch(step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = jit_step(state, batch)
            if step % 5 == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t_last
                t_last = time.perf_counter()
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):8.3f} ({dt:.1f}s)")
            if (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state, blocking=False)
        ckpt.wait()
        final_loss = float(metrics["loss"])
        print(f"[done] {args.steps} steps, final loss {final_loss:.4f}")
        return final_loss


if __name__ == "__main__":
    main()
