"""Training launcher with fault tolerance.

Design for a real fleet (documented here, exercised at container scale):

* **Checkpoint/restart**: async sharded checkpoints every `ckpt_every`
  steps; on (re)start the launcher restores the latest complete checkpoint
  and resumes at the recorded step.  The data pipeline is a pure function of
  the step index (data/lm.py), so resume is bitwise reproducible.
* **Watchdog**: the runner supervises the step loop; a step exceeding
  `step_timeout_s` (straggler / hung collective) aborts the attempt and
  restarts from the last checkpoint.  `max_restarts` bounds crash loops.
* **Elastic scaling**: `--mesh` accepts e.g. ``2x2`` (tests) up to
  ``16x16``/``2x16x16``; restore re-shards checkpoints onto whatever mesh
  the surviving fleet provides (ckpt/checkpoint.py saves unsharded arrays).
* **Inter-pod gradient compression**: --grad-compress enables int8
  error-feedback quantization ahead of the cross-pod reduction.

Usage (container-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --reduced --steps 20 --mesh 1x2 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.configs.registry import get_config
from repro.data import lm as lmdata
from repro.launch.mesh import parse_mesh
from repro.models import params as pmod
from repro.optim import adamw, compress
from repro.runtime import steps as steps_mod
from repro.runtime.sharding import tree_shardings


def train_loop(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = parse_mesh(args.mesh)
    shape = lmdata.ShapeSpec("train", args.seq, args.batch, "train")
    opt = adamw.OptConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                          accum_steps=args.accum, state_dtype=args.opt_dtype)
    batch0 = lmdata.batch_for_step(cfg, shape, 0)
    jitted, ctx, spec = steps_mod.jit_train_step(
        cfg, opt, mesh, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                     batch0), grad_compress=args.grad_compress)

    p_shard = tree_shardings(spec, ctx)
    params = pmod.initialize(jax.random.PRNGKey(args.seed), spec,
                             jnp.dtype(cfg.dtype))
    if mesh is not None:
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            params, p_shard)
    opt_state = adamw.init_state(params, opt)
    residual = compress.init_residual(params) if args.grad_compress else None

    start_step = 0
    ckptr = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckptr and not args.fresh:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state_like = {"params": params, "m": opt_state["m"],
                          "v": opt_state["v"], "step": opt_state["step"]}
            restored = ckpt.restore(args.ckpt_dir, latest, state_like,
                                    {"params": p_shard, "m": p_shard,
                                     "v": p_shard, "step": None})
            params = restored["params"]
            opt_state = {"m": restored["m"], "v": restored["v"],
                         "step": restored["step"]}
            start_step = latest
            print(f"[resume] restored step {latest} from {args.ckpt_dir}")

    losses = []
    t_start = time.time()
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = lmdata.batch_for_step(cfg, shape, step)
        if args.fail_at is not None and step == args.fail_at:
            raise RuntimeError(f"injected failure at step {step}")
        if args.grad_compress:
            params, opt_state, residual, loss, metrics = jitted(
                params, opt_state, batch, residual)
        else:
            params, opt_state, loss, metrics = jitted(params, opt_state, batch)
        loss = float(loss)
        dt = time.time() - t0
        if dt > args.step_timeout_s:
            raise TimeoutError(f"step {step} took {dt:.1f}s > {args.step_timeout_s}s "
                               "(straggler watchdog)")
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {loss:.4f} gnorm "
                  f"{float(metrics['grad_norm']):.3f} ({dt*1e3:.0f} ms)")
        if ckptr and (step + 1) % args.ckpt_every == 0:
            ckptr.save_async(step + 1, {"params": params, "m": opt_state["m"],
                                        "v": opt_state["v"],
                                        "step": opt_state["step"]})
    if ckptr:
        ckptr.wait()
    return {"final_loss": losses[-1] if losses else float("nan"),
            "losses": losses, "steps": args.steps - start_step,
            "wall_s": time.time() - t_start}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 / 16x16 / 2x16x16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--opt-dtype", default="float32")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--step-timeout-s", type=float, default=3600.0)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure (fault-tolerance tests)")
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args()

    # supervisor: restart from the latest checkpoint on failure
    for attempt in range(args.max_restarts + 1):
        try:
            out = train_loop(args)
            print(f"done: final_loss={out['final_loss']:.4f} "
                  f"wall={out['wall_s']:.1f}s")
            return
        except (RuntimeError, TimeoutError) as e:
            print(f"[watchdog] attempt {attempt} failed: {e}")
            if attempt == args.max_restarts or not args.ckpt_dir:
                raise
            args.fail_at = None   # injected failures fire once
            print("[watchdog] restarting from latest checkpoint...")


if __name__ == "__main__":
    main()
