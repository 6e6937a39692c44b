"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  - 512 placeholder host devices (merged into XLA_FLAGS below, BEFORE
    any jax import; an existing device-count flag or other user flags
    are respected, not clobbered)
  - 16x16 single-pod and 2x16x16 multi-pod production meshes
  - per cell: .lower() -> .compile() -> memory_analysis / cost_analysis /
    HLO roll-up costs (roofline terms), appended to a JSONL artifact.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k \
      --mesh single --out results.jsonl
  python -m repro.launch.dryrun --all --out results.jsonl   (driver mode:
      one subprocess per cell so XLA state/memory is isolated)
"""
import os

# A CPU-only tool on placeholder devices: it and the per-cell children it
# spawns (which inherit this environment) never take an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

from repro.launch.xla_env import force_host_device_count

force_host_device_count(512)

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch import shapes as shp
from repro.launch.mesh import make_production_mesh
from repro.models import decode as D
from repro.models import transformer as T
from repro.optim.optimizer import OptConfig
from repro.roofline import analysis as roof
from repro.roofline.hlo_costs import analyze_hlo
from repro.train.trainer import make_train_step


def _tree_named(tree_abs, spec_fn):
    """Build NamedShardings for a pytree of ShapeDtypeStructs."""

    def one(path, leaf):
        names = tuple(getattr(k, "key", str(k)) for k in path)
        return shd.named_safe(spec_fn(names, leaf.shape), leaf.shape)

    return jax.tree_util.tree_map_with_path(one, tree_abs)


def _batch_spec_fn(names, shape):
    if len(shape) == 1:
        return P(("pod", "data") if len(shape) else None)
    return P(("pod", "data"), *([None] * (len(shape) - 1)))


def _cache_spec_fn(cfg):
    kv_div = cfg.n_kv % 16 == 0

    def fn(names, shape):
        name = names[-1]
        if name in ("k", "v") and len(shape) == 5:
            # (L, B, S, KV, Dh)
            if shape[1] >= 16:
                return P(None, ("pod", "data"),
                         "model" if not kv_div else None,
                         "model" if kv_div else None, None)
            # tiny batch (long_500k): shard the cache sequence
            return P(None, None, ("data", "model"), None, None)
        if name == "state" and len(shape) == 5:     # mamba (L,B,H,N,P)
            return P(None, ("pod", "data") if shape[1] >= 16 else None,
                     "model" if shape[2] % 16 == 0 else None, None, None)
        if name == "conv" and len(shape) == 4:
            return P(None, ("pod", "data") if shape[1] >= 16 else None,
                     None, None)
        if name == "enc_out":
            return P(("pod", "data") if shape[0] >= 16 else None,
                     None, None)
        if len(shape) >= 2 and shape[1] >= 16:      # xlstm states (L,B,...)
            return P(None, ("pod", "data"),
                     *([None] * (len(shape) - 2)))
        return P(*([None] * len(shape)))

    return fn


def _parse_overrides(sets: list[str] | None) -> dict:
    out = {}
    for kv in sets or []:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        out[k] = v
    return out


def run_cell(arch: str, shape: str, mesh_kind: str,
             donate: bool = True, overrides: dict | None = None) -> dict:
    cfg0 = get_config(arch)
    applicable, why = shp.cell_applicable(cfg0, shape)
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                 "time": time.time()}
    if overrides:
        rec["overrides"] = dict(overrides)
    if not applicable:
        rec.update(status="skipped", reason=why)
        return rec

    cfg = shp.tune_for_shape(cfg0, shape)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    meta = shp.SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod"))
    chips = mesh.devices.size
    kind = meta["kind"]
    rules = shd.SERVE_RULES if kind == "decode" else None

    with shd.axis_rules(mesh, rules):
        p_abs = shp.abstract_params(cfg)
        p_sh = _tree_named(p_abs, shd.param_spec)

        if kind == "train":
            opt_abs = jax.eval_shape(
                lambda p: {"m": jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), p),
                    "v": jax.tree.map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), p),
                    "step": jnp.zeros((), jnp.int32)}, p_abs)
            opt_sh = {"m": p_sh, "v": p_sh,
                      "step": shd.named(P())}
            b_abs = shp.batch_specs(cfg, meta["seq"], meta["batch"],
                                    labels=True)
            b_sh = _tree_named(b_abs, _batch_spec_fn)
            fn = make_train_step(cfg, OptConfig())
            jfn = jax.jit(fn, in_shardings=(p_sh, opt_sh, b_sh),
                          out_shardings=(p_sh, opt_sh, None))
            lowered = jfn.lower(p_abs, opt_abs, b_abs)
            tokens = meta["seq"] * meta["batch"]
            # 6*N_active*D + 3x fwd attention (PaLM MFU convention)
            model_flops = roof.model_flops_train(
                cfg, tokens, seq=meta["seq"]) / chips

        elif kind == "prefill":
            b_abs = shp.batch_specs(cfg, meta["seq"], meta["batch"],
                                    labels=False)
            b_sh = _tree_named(b_abs, _batch_spec_fn)
            fn = lambda p, b: T.prefill(p, cfg, b)        # noqa: E731
            jfn = jax.jit(fn, in_shardings=(p_sh, b_sh),
                          out_shardings=shd.named(P(("pod", "data"), None)))
            lowered = jfn.lower(p_abs, b_abs)
            tokens = meta["seq"] * meta["batch"]
            model_flops = roof.model_flops_prefill(
                cfg, tokens, seq=meta["seq"]) / chips

        else:  # decode
            c_abs = shp.abstract_cache(cfg, meta["batch"], meta["seq"])
            c_sh = _tree_named(c_abs, _cache_spec_fn(cfg))
            tok_abs = jax.ShapeDtypeStruct((meta["batch"], 1), jnp.int32)
            pos_abs = jax.ShapeDtypeStruct((meta["batch"],), jnp.int32)
            tok_sh = shd.named(P(("pod", "data") if meta["batch"] >= 16
                                 else None, None))
            pos_sh = shd.named(P(("pod", "data") if meta["batch"] >= 16
                                 else None))
            fn = lambda p, c, t, pos: D.decode_step(p, cfg, c, t, pos)  # noqa: E731
            jfn = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                          out_shardings=(
                              shd.named(P(("pod", "data") if
                                          meta["batch"] >= 16 else None,
                                          None)), c_sh))
            lowered = jfn.lower(p_abs, c_abs, tok_abs, pos_abs)
            model_flops = roof.model_flops_decode(
                cfg, meta["batch"], meta["seq"]) / chips

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        try:
            cost = dict(compiled.cost_analysis())
        except Exception:
            cost = {}
        hlo = compiled.as_text()
        rolled = analyze_hlo(hlo)
        del hlo

    bytes_per_device = (mem.argument_size_in_bytes
                        + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes
                        - mem.alias_size_in_bytes)
    r = roof.analyze(
        arch, shape, mesh_kind, 1,
        {"flops": rolled["flops"], "bytes accessed": rolled["bytes"]},
        "", model_flops, bytes_per_device)
    r.coll_breakdown = {k: float(v)
                        for k, v in rolled["collectives"].items()}
    r.coll_bytes = float(sum(rolled["collectives"].values()))
    r.finish()

    rec.update(
        status="ok", chips=chips, compile_s=compile_s,
        memory=dict(
            argument=mem.argument_size_in_bytes,
            temp=mem.temp_size_in_bytes,
            output=mem.output_size_in_bytes,
            alias=mem.alias_size_in_bytes,
            per_device_total=bytes_per_device,
        ),
        cost_analysis={k: float(v) for k, v in cost.items()
                       if k in ("flops", "bytes accessed",
                                "transcendentals", "optimal_seconds")},
        rolled=dict(flops=rolled["flops"], bytes=rolled["bytes"],
                    collectives=rolled["collectives"]),
        roofline=r.to_json(),
    )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(shp.SHAPES))
    ap.add_argument("--mesh", choices=("single", "pod"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--set", action="append", default=[], dest="sets",
                    metavar="KEY=VALUE",
                    help="ArchConfig override(s) for perf iteration, "
                         "e.g. --set attn_impl=banded --set microbatch=8")
    args = ap.parse_args(argv)

    if args.all:
        done = set()
        try:
            for line in open(args.out):
                r = json.loads(line)
                if r.get("status") in ("ok", "skipped"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
        except FileNotFoundError:
            pass
        cells = [(a, s, m) for a in ARCHS for s in shp.SHAPES
                 for m in ("single", "pod")]
        for a, s, m in cells:
            if (a, s, m) in done:
                continue
            print(f"=== {a} x {s} x {m}", flush=True)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--out", args.out]
            try:
                subprocess.run(cmd, timeout=args.timeout, check=False)
            except subprocess.TimeoutExpired:
                with open(args.out, "a") as f:
                    f.write(json.dumps({"arch": a, "shape": s, "mesh": m,
                                        "status": "timeout"}) + "\n")
        return

    try:
        rec = run_cell(args.arch, args.shape, args.mesh,
                       overrides=_parse_overrides(args.sets))
    except Exception as e:  # record failures as artifacts too
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    status = rec.get("status")
    print(f"[{status}] {args.arch} x {args.shape} x {args.mesh}")
    if status == "ok":
        rl = rec["roofline"]
        print(f"  compile {rec['compile_s']:.1f}s | "
              f"bytes/dev {rec['memory']['per_device_total']/2**30:.2f}GiB"
              f" | t_comp {rl['t_compute']:.2e}s t_mem {rl['t_memory']:.2e}"
              f"s t_coll {rl['t_collective']:.2e}s -> {rl['bottleneck']}")
    elif status == "error":
        print(rec["error"])
        print(rec.get("trace", ""))
        sys.exit(1)


if __name__ == "__main__":
    main()
