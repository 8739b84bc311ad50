"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --smoke --steps 50 --batch 8 --seq 128

Fault-tolerance posture (exercised by tests/test_distribution.py):
  * atomic+async checkpoints every --ckpt-every steps (Checkpointer)
  * SIGTERM/SIGINT -> final checkpoint, clean exit (preemption survival)
  * resume: --resume picks up the latest step; the data pipeline is a pure
    function of step, so batches replay exactly (skip-ahead, no data state)
  * checkpoint cadence can be derived from a fleet MTBF via Young/Daly
    (--mtbf / --ckpt-cost) instead of a fixed interval
  * step watchdog: a step exceeding --step-timeout-s aborts with a
    checkpoint (straggler/hang mitigation — on a real fleet the launcher
    restarts the job on healthy nodes)
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.ckpt.checkpoint import Checkpointer
from repro.core.montecarlo import young_daly_interval
from repro.data.pipeline import DataConfig, get_batch
from repro.launch.mesh import make_local_mesh
from repro.sharding import partition
from repro.train import optim, step as step_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mtbf", type=float, default=0.0,
                    help="fleet MTBF seconds -> Young/Daly cadence")
    ap.add_argument("--ckpt-cost", type=float, default=5.0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--step-timeout-s", type=float, default=0.0)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    cfg = dataclasses.replace(cfg, microbatches=1)
    mesh = make_local_mesh(args.data, args.model)
    multi = mesh.devices.size > 1

    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch, seed=args.seed)
    opt_cfg = optim.AdamWConfig(lr=args.lr, total_steps=args.steps)
    train_step = step_lib.make_train_step(cfg, mesh if multi else None,
                                          opt_cfg)

    state = step_lib.init_state(cfg, jax.random.key(args.seed))
    shardings = None
    if multi:
        shardings, _ = step_lib.state_shardings(cfg, mesh)
        state = jax.device_put(state, shardings)

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, start = ckpt.restore(state, shardings=shardings)
        print(f"resumed from step {start}")

    every = args.ckpt_every
    if args.mtbf > 0:
        # steps-per-checkpoint from Young/Daly given measured step time
        every = max(1, int(young_daly_interval(args.mtbf, args.ckpt_cost)))
        print(f"Young/Daly cadence: checkpoint every ~{every}s of compute")

    stop = {"flag": False}

    def on_term(signum, frame):
        stop["flag"] = True
    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    jit_step = jax.jit(train_step, donate_argnums=(0,))
    losses = []
    ctx = jax.set_mesh(mesh) if multi else None
    if ctx:
        ctx.__enter__()
    try:
        for step in range(start, args.steps):
            batch = get_batch(dc, step)
            t0 = time.time()
            state, metrics = jit_step(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            if args.step_timeout_s and dt > args.step_timeout_s:
                print(f"WATCHDOG: step {step} took {dt:.1f}s "
                      f"> {args.step_timeout_s}s; checkpoint + abort")
                if ckpt:
                    ckpt.save(state, step + 1, blocking=True)
                return 42
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if ckpt and (step + 1) % every == 0:
                ckpt.save(state, step + 1, blocking=False)
            if stop["flag"]:
                print(f"SIGTERM at step {step}: checkpointing and exiting")
                if ckpt:
                    ckpt.save(state, step + 1, blocking=True)
                return 0
        if ckpt:
            ckpt.save(state, args.steps, blocking=True)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)
        if ckpt:
            ckpt.wait()

    if len(losses) >= 20:
        a = float(np.mean(losses[:5]))
        b = float(np.mean(losses[-5:]))
        print(f"loss first5={a:.4f} last5={b:.4f} "
              f"({'DECREASED' if b < a else 'no decrease'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
