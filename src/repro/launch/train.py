"""Training launcher: mesh setup, sharded init, checkpoint/restart,
preemption handling, elastic rescale.

Examples (CPU-scale):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --reduced \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ck --ckpt-every 20

Fault-tolerance semantics:
  * SIGTERM/SIGUSR1 → checkpoint + clean exit (preemption).
  * restart with the same --ckpt-dir resumes from the latest step.
  * restarting under a different device count / mesh shape just works —
    checkpoints are unsharded global arrays (ckpt/manager.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager, install_sigterm_handler
from repro.configs import get_config
from repro.data.synth import DataConfig, synth_batch
from repro.distributed.sharding import Boxed, is_boxed, param_pspecs
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.launch.shapes import init_fn_for
from repro.train.optim import OptimConfig, init_opt_state
from repro.train.step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "bf16", "int8_ef"))
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--mesh", default="none",
                    choices=("none", "smoke", "single", "multi"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    cfg = cfg.replace(attn_chunk=min(cfg.attn_chunk, args.seq))

    opt_cfg = OptimConfig(lr=args.lr, weight_decay=args.weight_decay,
                          total_steps=args.steps,
                          warmup_steps=max(args.steps // 20, 1),
                          grad_compression=args.grad_compression)
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      seed=args.seed)

    mesh = None
    if args.mesh == "smoke":
        mesh = make_smoke_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    flag = install_sigterm_handler()
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    def build_state():
        key = jax.random.PRNGKey(args.seed)
        params = init_fn_for(cfg)(key, cfg)
        return params, init_opt_state(params, opt_cfg)

    ctx = jax.set_mesh(mesh) if mesh is not None else None
    if ctx is not None:
        ctx.__enter__()
    try:
        params, opt_state = build_state()
        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            start_step = mgr.latest_step()
            state = mgr.restore(start_step,
                                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step}")

        step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                          grad_accum=args.grad_accum),
                          donate_argnums=(0, 1))

        t_start = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in synth_batch(cfg, dcfg, step).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)

            if (step + 1) % args.log_every == 0 or step == start_step:
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                tput = dcfg.global_batch * dcfg.seq_len * \
                    (step + 1 - start_step) / max(time.time() - t_start,
                                                  1e-9)
                print(f"[train] step={step + 1} loss={loss:.4f} "
                      f"gnorm={gn:.3f} tok/s={tput:,.0f}", flush=True)

            should_ckpt = mgr is not None and (
                (step + 1) % args.ckpt_every == 0 or flag.triggered
                or step + 1 == args.steps)
            if should_ckpt:
                mgr.save(step + 1, {"params": params, "opt": opt_state},
                         block=flag.triggered)
            if flag.triggered:
                print(f"[train] preempted at step {step + 1}; "
                      "checkpoint written, exiting")
                break
        if mgr is not None:
            mgr.wait()
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    return params


if __name__ == "__main__":
    main()
