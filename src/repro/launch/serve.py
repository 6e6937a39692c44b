"""Distributed serving launcher: mesh + TP-only weight shardings +
DecodeEngine (serve rules: no per-layer FSDP gathers on the decode path).

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
      --smoke --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config, get_smoke
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.xla_env import setup_compile_cache
from repro.models import transformer as T
from repro.serve import DecodeEngine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--pool", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    with shd.axis_rules(mesh, shd.SERVE_RULES):
        p_abs = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg))
        p_sh = jax.tree_util.tree_map_with_path(
            lambda path, l: shd.named_safe(
                shd.param_spec(tuple(getattr(k, "key", str(k))
                                     for k in path), l.shape), l.shape),
            p_abs)
        params = jax.jit(lambda: T.init_params(
            jax.random.PRNGKey(0), cfg), out_shardings=p_sh)()
        engine = DecodeEngine(cfg, params, batch=args.pool,
                              max_len=args.max_len)
        for i in range(args.requests):
            engine.submit(Request(
                prompt=[2 + i, 7, (11 * i + 3) % cfg.vocab],
                max_new=args.max_new))
        t0 = time.time()
        done = engine.run()
        dt = time.time() - t0
    total = sum(len(r.out) for r in done)
    print(f"{len(done)} requests, {total} tokens, {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. compile) on mesh {args.mesh}")


if __name__ == "__main__":
    main()
