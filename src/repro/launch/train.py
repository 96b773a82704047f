"""End-to-end QAT training driver.

CPU-runnable on reduced configs (``--smoke``); the same code path drives the
production mesh on real hardware (the dry-run proves those shardings
compile). Fault tolerance comes from dist/fault.py: checkpoint-every-k,
restore-on-crash, deterministic data by (seed, step).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --smoke \
      --steps 20 --optimizer int8_adam --compressed-dp
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro import optim
from repro.configs import SHAPES, ShapeConfig, get_config, reduce_for_smoke
from repro.core.qlinear import QuantPolicy
from repro.data import make_pipeline
from repro.dist.fault import FaultConfig, run_resilient
from repro.launch import compile_cache, steps as St


def build(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
        cfg = dataclasses.replace(cfg, microbatch=min(cfg.microbatch, 2))
    if args.w_bits:
        cfg = dataclasses.replace(
            cfg, quant=QuantPolicy(w_bits=args.w_bits,
                                   a_bits=args.a_bits or None))
    shape = ShapeConfig("custom", args.seq, args.batch, "train") \
        if args.smoke else SHAPES["train_4k"]

    opt_fn = optim.OPTIMIZERS[args.optimizer] if hasattr(optim, "OPTIMIZERS") \
        else optim.adamw
    from repro.optim.optimizers import OPTIMIZERS
    opt = OPTIMIZERS[args.optimizer](
        optim.warmup_cosine(args.lr, args.warmup, args.steps))
    return cfg, shape, opt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, CPU-runnable")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "int8_adam", "adafactor", "sgd"))
    ap.add_argument("--w-bits", type=int, default=2)
    ap.add_argument("--a-bits", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--compressed-dp", action="store_true",
                    help="int8 error-feedback gradient all-reduce over the "
                         "data axis (dist.collectives.compressed_psum)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg, shape, opt = build(args)
    print(f"[train] {cfg.name}: {cfg.n_params()/1e6:.1f}M params, "
          f"QAT w{cfg.quant.w_bits}a{cfg.quant.a_bits or 16}, "
          f"{shape.global_batch}x{shape.seq_len} tokens/step")

    key = jax.random.PRNGKey(args.seed)
    state = St.init_train_state(key, cfg, opt, mode="qat")
    if args.compressed_dp:
        from repro.launch.mesh import make_cpu_mesh
        n_dp = len(jax.devices())
        assert shape.global_batch % n_dp == 0, (shape.global_batch, n_dp)
        mesh = make_cpu_mesh((n_dp,), ("data",))
        state["dp_err"] = St.init_dp_err(state["params"], n_dp)
        print(f"[train] compressed DP all-reduce over {n_dp} replicas "
              f"(int8 block-64 wire + error feedback)")
        step_fn = jax.jit(St.make_dp_train_step(cfg, opt, mesh, mode="qat",
                                                compressed=True),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(St.make_train_step(cfg, opt, mode="qat"),
                          donate_argnums=(0,))
    pipe = make_pipeline(cfg, shape, seed=args.seed)

    fc = FaultConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    t0 = time.time()

    def on_metrics(m):
        if m["step"] % args.log_every == 0:
            print(f"  step {m['step']:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"({m['dt']*1e3:.0f} ms)", flush=True)

    state, log = run_resilient(state, step_fn, pipe.batch, args.steps, fc,
                               on_metrics=on_metrics)
    dt = time.time() - t0
    losses = [float(m["loss"]) for m in log]
    print(f"[train] done: {len(log)} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
