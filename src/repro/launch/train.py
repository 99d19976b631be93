"""Training driver.

Real-hardware entry point and CPU-reduced end-to-end path (the smoke
examples train a ~100M-param-class reduced model for a few hundred
steps). Fault tolerance: checkpoint/restart supervisor + in-step
NaN-guard; deterministic step-addressed data pipeline.

  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --reduced \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs.registry import get_config, reduced
from repro.data.pipeline import FrontendPipeline, TokenPipeline
from repro.ft.restart import run_with_restarts
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.sharding import make_rules
from repro.optim.adamw import OptConfig, init_opt
from repro.train.trainer import TrainConfig, make_train_step


def build(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    rules = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model")[:len(shape)] if len(shape) <= 2 else \
            ("pod", "data", "model")
        mesh = jax.make_mesh(shape, axes)
        rules = make_rules(cfg, mesh, kind="train")
    opts = T.ModelOpts(remat=args.remat, loss_chunk=args.loss_chunk)
    oc = OptConfig(lr_max=args.lr, warmup=args.warmup,
                   decay_steps=args.steps)
    tc = TrainConfig(grad_accum=args.grad_accum)
    step_fn = jax.jit(make_train_step(cfg, oc, tc, rules=rules, opts=opts),
                      donate_argnums=(0, 1))
    pipe = TokenPipeline(cfg.vocab_size, args.batch, args.seq,
                         seed=args.seed)
    fpipe = None
    if cfg.frontend == "vision":
        fpipe = FrontendPipeline(cfg.d_model, cfg.frontend_tokens,
                                 seed=args.seed)
    elif cfg.frontend == "audio":
        fpipe = FrontendPipeline(cfg.d_model, args.seq, seed=args.seed)
    return cfg, oc, step_fn, pipe, fpipe, mesh


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--mesh", default="", help="e.g. 2,4 for (data,model)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)

    cfg, oc, step_fn, pipe, fpipe, _ = build(args)
    key = jax.random.PRNGKey(args.seed)
    history = []

    def batch_at(step):
        b = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        if fpipe is not None:
            b["frontend"] = jnp.asarray(fpipe.batch_at(step, args.batch))
        return b

    def init_state():
        params = T.init_params(cfg, key)
        return 0, (params, init_opt(params, oc))

    def run_step(step, state):
        params, opt = state
        params, opt, m = step_fn(params, opt, batch_at(step))
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(m["loss"])
            history.append({"step": step, "loss": loss,
                            "grad_norm": float(m["grad_norm"]),
                            "skipped": int(m["skipped"])})
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f}", flush=True)
        return params, opt

    if args.ckpt_dir:
        def restore_state(latest):
            params = T.init_params(cfg, key)
            st, tree, _ = ckpt.restore(
                args.ckpt_dir, {"params": params,
                                "opt": init_opt(params, oc)})
            return st, (tree["params"], tree["opt"])

        def save_state(step, state):
            ckpt.save(args.ckpt_dir, step,
                      {"params": state[0], "opt": state[1]})

        step, state, stats = run_with_restarts(
            init_state=init_state, restore_state=restore_state,
            run_step=run_step, save_state=save_state,
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every)
        print(f"done at step {step}; restarts={stats.restarts}")
    else:
        step, state = init_state()
        t0 = time.time()
        while step < args.steps:
            state = run_step(step, state)
            step += 1
        dt = time.time() - t0
        print(f"done: {args.steps} steps in {dt:.1f}s "
              f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
