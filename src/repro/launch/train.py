"""Distributed training launcher: mesh + FSDP/TP shardings + Trainer.

Single-host CPU: runs the reduced configs directly.  On a TPU pod the
same entrypoint runs under `jax.distributed.initialize()` with the
production mesh (each host feeds its data shard; the train step is one
SPMD program).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 20 --seq 128 --batch 8 --mesh 1x1
"""
from __future__ import annotations

import argparse
import logging

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config, get_smoke
from repro.launch import mesh as mesh_lib
from repro.launch.xla_env import setup_compile_cache
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed import sharding as shd
from repro.models import transformer as T
from repro.optim.optimizer import OptConfig, init_opt_state
from repro.train import checkpoint as ckpt
from repro.train.fault import RetryPolicy, StragglerMonitor
from repro.train.trainer import TrainConfig, make_train_step

log = logging.getLogger("repro.launch.train")


def make_mesh(spec: str):
    """'DxM' -> mesh over (data, model); '1x1' works on one device."""
    d, m = (int(x) for x in spec.split("x"))
    return mesh_lib.make_mesh((d, m), ("data", "model"))


def shardings_for(mesh, cfg, seq: int, batch: int):
    """(param, opt, batch) NamedShardings under the FSDP+TP rules."""
    with shd.axis_rules(mesh):
        p_abs = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg))
        p_sh = jax.tree_util.tree_map_with_path(
            lambda path, l: shd.named_safe(
                shd.param_spec(tuple(getattr(k, "key", str(k))
                                     for k in path), l.shape), l.shape),
            p_abs)
        opt_sh = {"m": p_sh, "v": p_sh, "step": shd.named(P())}
        b_sh = shd.named_safe(P("data"), (batch, seq))
    return p_sh, opt_sh, b_sh


def run(args) -> dict:
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh(args.mesh)
    opt_cfg = OptConfig(lr=args.lr, warmup=min(50, args.steps // 5 or 1),
                        total_steps=args.steps)
    p_sh, opt_sh, b_sh = shardings_for(mesh, cfg, args.seq, args.batch)

    pipe = TokenPipeline(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch, seed=args.seed,
                   d_model=cfg.d_model,
                   n_prefix=cfg.n_prefix if cfg.frontend == "vision" else 0,
                   src_len=64 if cfg.frontend == "audio" else 0),
        process_index=jax.process_index(),
        process_count=jax.process_count())

    with shd.axis_rules(mesh):
        step_fn = jax.jit(make_train_step(cfg, opt_cfg),
                          in_shardings=(p_sh, opt_sh, None),
                          out_shardings=(p_sh, opt_sh, None),
                          donate_argnums=(0, 1))
        params = jax.jit(lambda: T.init_params(
            jax.random.PRNGKey(args.seed), cfg), out_shardings=p_sh)()
        opt_state = init_opt_state(params)
        opt_state = jax.device_put(opt_state, opt_sh)

        start = 0
        last = ckpt.latest_step(args.ckpt)
        if last is not None:
            _, st = ckpt.load(args.ckpt, last,
                              shardings={"params": p_sh, "opt": opt_sh})
            params, opt_state = st["params"], st["opt"]
            start = last
            log.info("resumed at step %d", start)

        retry = RetryPolicy()
        straggler = StragglerMonitor()
        losses = []
        import time as _time
        for step in range(start, args.steps):
            batch = pipe.device_batch(step)
            t0 = _time.perf_counter()
            params, opt_state, metrics = retry.run(
                lambda b=batch: step_fn(params, opt_state, b))
            dt = _time.perf_counter() - t0
            straggler.observe(step, dt)
            losses.append(float(metrics["loss"]))
            if step % 10 == 0:
                print(f"step {step} loss {losses[-1]:.4f} ({dt:.2f}s)",
                      flush=True)
            if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
                ckpt.save(args.ckpt, step + 1,
                          {"params": params, "opt": opt_state},
                          blocking=(step + 1 == args.steps))
    return {"losses": losses, "stragglers": straggler.flagged_steps}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args(argv)
    setup_compile_cache()
    out = run(args)
    ls = out["losses"]
    if ls:
        print(f"loss {ls[0]:.4f} -> {ls[-1]:.4f} over {len(ls)} steps")


if __name__ == "__main__":
    main()
