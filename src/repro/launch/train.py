"""End-to-end training driver.

The training loop is Koalja circuitry end to end: batches arrive as
AnnotatedValues from the data pipeline, each optimizer step is a SmartTask
execution stamped into the provenance registry, and checkpoints are AVs
whose travel documents name the exact code version, config and data batches
that produced them. Fault tolerance is make-mode: on (simulated) failure the
driver restores the latest checkpoint AV and replays.

CPU quickstart (reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 20 --batch 8 --seq 128

The train state is made sharded on the mesh, never whole on one device.
Each step's time ends in ``block_until_ready``; ``--ckpt-every 0`` turns
checkpoints off.
"""

from __future__ import annotations

import argparse
import functools
import os
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import ProvenanceRegistry, software_version_of
from repro.data.pipeline import build_data_pipeline, next_batch
from repro.dist.ft import FaultToleranceManager, SimulatedFailure
from repro.dist.step import init_train_state
from repro.launch.device import device_info, enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model, train_loss
from repro.optim import cosine_warmup
from repro.workspace import MeshExecutor


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument(
        "--ckpt-dir", default=os.path.join(tempfile.gettempdir(), "repro_ckpt")
    )
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a simulated host failure (tests recovery)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device {dev['platform']} {dev['kind']} x{dev['count']}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    schedule = cosine_warmup(args.lr, max(2, args.steps // 10), args.steps)

    # the executor backend owns the mesh + sharding rules; the same call
    # targets a production mesh by swapping the executor, nothing else
    executor = MeshExecutor(
        make_host_mesh(), cfg=cfg, mode="train", global_batch=args.batch
    )
    jitted, state_shapes, state_shard, batch_shard = executor.train_step(
        model, schedule, microbatches=args.microbatches
    )

    registry = ProvenanceRegistry()
    sw = software_version_of(train_loss)
    registry.register_task("train_step", ["batch"], ["state", "metrics"], sw)
    ckpt = CheckpointManager(args.ckpt_dir, software_version=sw)
    data = build_data_pipeline(cfg, args.batch, args.seq, seed=args.seed)
    ft = FaultToleranceManager(n_hosts=jax.process_count())

    def fresh_state():
        return jax.jit(
            functools.partial(init_train_state, model), out_shardings=state_shard
        )(jax.random.key(args.seed))

    def restore():
        last = ckpt.latest_step()
        if args.resume and last is not None:
            state, manifest = ckpt.restore(fresh_state())
            print(f"[restore] step {last} (sw={manifest['software_version']})")
            return state, last
        return fresh_state(), 0

    def run(start_state, start_step):
        state = start_state
        for step in range(start_step, args.steps):
            t0 = time.perf_counter()
            batch = next_batch(data, cfg)
            batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            if cfg.encoder_layers and "frames" not in batch:
                batch["frames"] = jax.numpy.asarray(
                    np.random.RandomState(step).randn(
                        args.batch, cfg.frontend_len, cfg.d_model
                    ),
                    dtype=jax.numpy.float32,
                )
            if cfg.frontend == "vision" and "prefix" not in batch:
                batch["prefix"] = jax.numpy.asarray(
                    np.random.RandomState(step).randn(
                        args.batch, cfg.frontend_len, cfg.d_model
                    ),
                    dtype=jax.numpy.float32,
                )
            state, metrics = jitted(state, batch)
            jax.block_until_ready(metrics)
            dt = time.perf_counter() - t0
            ft.heartbeat(0, dt)
            registry.log_visit("train_step", f"step-{step}", "executed", sw,
                               note=f"loss={float(metrics['loss']):.4f} wall={dt:.3f}s")
            if step == args.fail_at_step:
                ckpt.wait()
                raise SimulatedFailure(host=0, msg=f"injected at step {step}")
            print(
                f"step {step:5d} loss {float(metrics['loss']):.4f} "
                f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.3f} "
                f"({dt:.2f}s)"
            )
            if args.ckpt_every and (
                (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps
            ):
                ckpt.save_async(state, step + 1, meta={"loss": float(metrics["loss"])})
        ckpt.wait()
        return state

    # make-mode recovery loop
    attempts = 0
    while True:
        state, start = restore()
        try:
            state = run(state, start)
            break
        except SimulatedFailure as e:
            attempts += 1
            args.resume = True
            args.fail_at_step = -1  # replacement host joins; don't re-fail
            print(f"[ft] {e} -> restart from latest checkpoint (attempt {attempts})")
            if attempts > 3:
                raise

    print(f"[done] {args.steps} steps; checkpoints: {[a.meta['step'] for a in ckpt.saved]}")
    print(f"[provenance] visitor log entries: {len(registry.visitor_log('train_step'))}")
    return state


if __name__ == "__main__":
    main()
