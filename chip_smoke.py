#!/usr/bin/env python3
"""Bring-up smoke run of the served solver stack on a TPU.

Drives ``repro.serve.SolverMux`` through its user entry points
(``submit`` / ``submit_dag`` / ``attach_decode`` + ``submit_decode``)
with Pallas kernels compiled natively for the chip, and checks every
answer against the ``repro.kernels.ref`` oracles, computed on the same
chip at ``highest`` matmul precision.  Inputs come only from ``--seed``.

Phases, in order (one line each):

  slot    one NR slot's equalisation bulk: 273 MMSE jobs, one per PRB
          (100 MHz at 30 kHz SCS, TS 38.101-1), n = 16 layers,
          m = 20 antennas, k = 2 symbols; half as 2-plane jobs (``base``)
          and half as split re/im 4-plane jobs (``split_complex``), plus
          control-path ``cholesky_solve`` / ``qr_solve`` jobs at every
          registered small size
  large   ``cholesky_solve`` / ``qr_solve`` / ``mmse_equalize`` at
          n = 256 (``blocked``; ``base`` for MMSE, which has no blocked
          variant) and n = 512, 1024 (``tiled``)
  dag     ``pusch_receive`` DAGs, staged and chained
  decode  ``lm_decode`` on the phi4-mini SMOKE preset, 4 requests

Each phase runs twice through the same mux on the host's monotonic
clock: the first pass compiles, the second is warm.  Every job is
``hard`` with no deadline.  The run fails (non-zero exit, no result
line) if any job or DAG ends in a state other than ``done``, if the
mux's event log shows a retry, failure, demotion or quarantine, if an
error exceeds its tolerance, if JAX's default device is not a TPU, or if
Pallas would run in interpret mode.  Its last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--chips 4`` runs only the slot phase, at ``mesh_size=4`` and at
``mesh_size=1``, compares the two sets of outputs, prints each lane
shard's launch count and device, and fails unless 4 distinct devices
did work.

    python chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Tolerances on max_j ||x_j - oracle_j||_max / ||oracle_j||_max.
# Small systems: float32 backward-stable solves of SPD / least-squares /
# MMSE systems with condition numbers up to ~300 (Gaussian 20x16
# channels, sigma2 = 0.1) land near cond * n * eps32 ~ 6e-4 at worst.
TOL_SMALL = 1e-3
# n >= 256: longer accumulation chains, and least squares error scales
# with cond(A)^2 (~130^2 for Gaussian (n+16) x n at n = 512).
TOL_LARGE = 2e-3
# Mesh-spanning vs single-device: the same kernel on the same lanes, so
# only a different launch composition separates them.
TOL_MESH = 1e-6
# Decode: the model computes in bfloat16 (8-bit mantissa), the cached
# step and the full-forward reference round differently; a served token
# must be within this share of the logit range of the reference argmax.
TOL_DECODE = 5e-2

SLOT_PRBS = 273              # 100 MHz at 30 kHz SCS (TS 38.101-1)
SLOT_N, SLOT_M, SLOT_K = 16, 20, 2
CONTROL_SIZES = (8, 12, 16, 24, 32)
CONTROL_JOBS = 4             # per pipeline and size
LARGE_SIZES = (256, 512, 1024)
LARGE_JOBS = 2               # per pipeline and size
DAG_SIZES = (8, 12)
DAGS_PER_MODE = 2            # per size, staged and chained each
DECODE_REQUESTS = 4
# the supervision ladder, lost work, and a log overflow that could hide
# either
BAD_EVENTS = ("retry", "fail", "demote", "quarantine", "drop",
              "events_dropped")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def import_repro():
    """Import the program from this checkout's ``src`` only."""
    sys.path.insert(0, SRC)
    try:
        import repro
    except ImportError as e:
        fail(f"cannot import the program from {SRC}: {e}")
    where = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(SRC + os.sep) for p in where):
        fail(f"repro resolved outside this checkout: {where}")
    return repro


# ---------------------------------------------------------------------------
# Job generation (numpy, from the seed) and oracle checks
# ---------------------------------------------------------------------------

def slot_jobs(rng):
    from repro.kernels.common import sample_spd
    f32 = lambda *s: rng.standard_normal(s).astype("float32")  # noqa: E731
    jobs = []
    for prb in range(SLOT_PRBS):
        if prb % 2 == 0:
            jobs.append(("mmse_equalize",
                         (f32(SLOT_M, SLOT_N), f32(SLOT_M, SLOT_K))))
        else:
            jobs.append(("mmse_equalize",
                         (f32(SLOT_M, SLOT_N), f32(SLOT_M, SLOT_N),
                          f32(SLOT_M, SLOT_K), f32(SLOT_M, SLOT_K))))
    for n in CONTROL_SIZES:
        for _ in range(CONTROL_JOBS):
            jobs.append(("cholesky_solve",
                         (sample_spd(rng, 1, n)[0], f32(n, 2))))
            jobs.append(("qr_solve", (f32(n + 4, n), f32(n + 4, 2))))
    return jobs


def large_jobs(rng):
    from repro.kernels.common import sample_spd
    f32 = lambda *s: rng.standard_normal(s).astype("float32")  # noqa: E731
    jobs = []
    for n in LARGE_SIZES:
        for _ in range(LARGE_JOBS):
            jobs.append(("cholesky_solve",
                         (sample_spd(rng, 1, n)[0], f32(n, 2))))
            jobs.append(("qr_solve", (f32(n + 16, n), f32(n + 16, 2))))
            jobs.append(("mmse_equalize", (f32(n + 16, n), f32(n + 16, 2))))
    return jobs


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                 1e-30))


def check_jobs(K, jobs: list) -> float:
    """Max relative error of served ``SolveJob`` outputs against their
    dispatched variant's oracle, one batched oracle call per shape
    bucket, at ``highest`` matmul precision."""
    import jax
    import numpy as np
    groups: dict = {}
    for job in jobs:
        groups.setdefault((job.pipeline, job.shape_key()), []).append(job)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for (pipeline, _), group in groups.items():
            spec = K.get(pipeline)
            args0 = group[0].args
            variant = spec.dispatch_key(tuple(a.shape for a in args0),
                                        tuple(a.dtype for a in args0))
            oracle = variant.oracle or spec.run_oracle
            batched = [np.stack([j.args[i] for j in group])
                       for i in range(len(args0))]
            want = np.asarray(oracle(*batched))
            for j, w in zip(group, want):
                worst = max(worst, rel_err(j.out, w))
    return worst


def check_events(mux) -> list:
    events = mux.drain_events()
    bad = [e for e in events if e.get("event") in BAD_EVENTS]
    if bad:
        fail(f"supervision ladder used: {bad[:5]}")
    return events


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def serve_jobs(mux, specs_args: list) -> tuple[list, float]:
    """One pass: submit every job (hard, no deadline), drain, time it."""
    t0 = time.monotonic()
    jobs = [mux.submit(p, *args, priority="hard") for p, args in specs_args]
    mux.run()
    dt = time.monotonic() - t0
    bad = [(j.pipeline, j.state, j.reason) for j in jobs
           if j.state != "done"]
    if bad:
        fail(f"{len(bad)} jobs not done: {bad[:5]}")
    return jobs, dt


def run_solver_phase(name: str, K, mux, make_jobs, rng_seed: int,
                     tol: float) -> dict:
    import numpy as np
    walls, err = [], 0.0
    for rep in range(2):                 # compile pass, then warm pass
        mux.reset_metrics()
        rng = np.random.default_rng([rng_seed, rep])
        jobs, dt = serve_jobs(mux, make_jobs(rng))
        walls.append(dt)
        check_events(mux)
        err = max(err, check_jobs(K, jobs))
        snap = mux.metrics()
    counts = {p: dict(s.dispatch_counts) for p, s in snap.pipelines.items()}
    if err > tol:
        fail(f"{name}: max relative error {err:.3e} > tolerance {tol:.0e}")
    report(name, jobs=snap.total_jobs, launches=snap.total_launches,
           dispatch_counts=counts, max_rel_err=err, tol=tol,
           compile_s=walls[0] - walls[1], warm_wall_s=walls[1])
    return counts


def phase_slot(K, seed: int) -> None:
    from repro.serve import SolverMux
    counts = run_solver_phase("slot", K, SolverMux(lanes=8), slot_jobs,
                              seed, TOL_SMALL)
    mmse = counts.get("mmse_equalize", {})
    if not (mmse.get("base") and mmse.get("split_complex")):
        fail(f"slot: mmse_equalize variants {mmse}, want base and "
             "split_complex")


def phase_large(K, seed: int) -> None:
    from repro.serve import SolverMux
    counts = run_solver_phase("large", K, SolverMux(lanes=LARGE_JOBS),
                              large_jobs, seed + 1, TOL_LARGE)
    want = {"cholesky_solve": ("blocked", "tiled"),
            "qr_solve": ("blocked", "tiled"),
            "mmse_equalize": ("base", "tiled")}
    for pipeline, variants in want.items():
        got = counts.get(pipeline, {})
        if not all(got.get(v) for v in variants):
            fail(f"large: {pipeline} variants {got}, want {variants}")


def phase_dag(K, seed: int) -> None:
    import jax
    import numpy as np
    from repro.serve import SolverMux
    spec = K.get_dag("pusch_receive")
    mux = SolverMux(lanes=8)
    walls, err, snap = [], 0.0, None
    for rep in range(2):
        mux.reset_metrics()
        rng = np.random.default_rng([seed + 2, rep])
        t0 = time.monotonic()
        dags = [(mux.submit_dag("pusch_receive", *args, priority="hard",
                                chained=chained), args)
                for chained in (False, True) for n in DAG_SIZES
                for args in [spec.make_case(rng, n)
                             for _ in range(DAGS_PER_MODE)]]
        mux.run()
        walls.append(time.monotonic() - t0)
        bad = [(d.chained, d.state, d.reason) for d, _ in dags
               if d.state != "done"]
        if bad:
            fail(f"dag: {len(bad)} DAGs not done: {bad[:5]}")
        check_events(mux)
        with jax.default_matmul_precision("highest"):
            for d, args in dags:
                err = max(err, rel_err(d.out, spec.oracle(*args)))
        snap = mux.metrics()
    if err > spec.rtol:
        fail(f"dag: max relative error {err:.3e} > tolerance "
             f"{spec.rtol:.0e}")
    counts = {p: dict(s.dispatch_counts) for p, s in snap.pipelines.items()}
    report("dag", dags=len(dags), jobs=snap.total_jobs,
           launches=snap.total_launches, dispatch_counts=counts,
           max_rel_err=err, tol=spec.rtol,
           compile_s=walls[0] - walls[1], warm_wall_s=walls[1])


def phase_decode(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke
    from repro.models import transformer as T
    from repro.serve import SolverMux
    from repro.serve.decode import DecodeEngine, Request
    cfg = get_smoke("phi4-mini-3.8b")
    params = T.init_params(jax.random.key(seed), cfg)
    mux = SolverMux(lanes=8)
    mux.attach_decode(DecodeEngine(cfg, params, batch=DECODE_REQUESTS,
                                   max_len=64, eos_id=-1, seed=seed))
    ref = jax.jit(lambda toks: T.prefill(params, cfg, {"tokens": toks}))
    walls, err, tokens = [], 0.0, 0
    for rep in range(2):
        mux.reset_metrics()
        rng = np.random.default_rng([seed + 3, rep])
        reqs = [Request(prompt=[int(t) for t in
                                rng.integers(2, cfg.vocab, 3 + i)],
                        max_new=8) for i in range(DECODE_REQUESTS)]
        t0 = time.monotonic()
        for r in reqs:
            mux.submit_decode(r, priority="hard")
        mux.run()
        walls.append(time.monotonic() - t0)
        bad = [(r.seq, r.done, r.dropped) for r in reqs
               if not r.done or r.dropped or len(r.out) != r.max_new]
        if bad:
            fail(f"decode: {len(bad)} requests not done: {bad}")
        check_events(mux)
        # every served (greedy) token must be a reference argmax up to
        # bfloat16 rounding: its full-forward logit within TOL_DECODE of
        # the best, relative to the logit range
        with jax.default_matmul_precision("highest"):
            for r in reqs:
                seq = list(r.prompt)
                for tok in r.out:
                    lg = np.asarray(ref(jnp.asarray([seq], jnp.int32)))[0]
                    span = max(float(lg.max() - lg.min()), 1e-30)
                    err = max(err, float(lg.max() - lg[tok]) / span)
                    seq.append(tok)
                    tokens += 1
        snap = mux.metrics()
    if err > TOL_DECODE:
        fail(f"decode: served token off the reference argmax by {err:.3e}"
             f" of the logit range > {TOL_DECODE:.0e}")
    report("decode", requests=DECODE_REQUESTS, tokens=snap.decode.tokens,
           steps=snap.decode.steps, launches=snap.total_launches,
           dispatch_counts={"lm_decode": {"base": snap.decode.steps}},
           max_rel_err=err, tol=TOL_DECODE,
           compile_s=walls[0] - walls[1], warm_wall_s=walls[1])


def phase_mesh(K, seed: int) -> None:
    """Slot phase at mesh_size=4 against mesh_size=1 on the same jobs."""
    import numpy as np
    from repro.serve import SolverMux
    outs = {}
    for mesh in (4, 1):
        mux = SolverMux(lanes=8, mesh_size=mesh)
        walls = []
        for rep in range(2):
            mux.reset_metrics()
            jobs, dt = serve_jobs(
                mux, slot_jobs(np.random.default_rng([seed, rep])))
            walls.append(dt)
            check_events(mux)
        err = check_jobs(K, jobs)
        if err > TOL_SMALL:
            fail(f"mesh{mesh}: max relative error {err:.3e}")
        outs[mesh] = [j.out for j in jobs]
        snap = mux.metrics()
        fields = dict(jobs=snap.total_jobs, launches=snap.total_launches,
                      max_rel_err=err, tol=TOL_SMALL,
                      compile_s=walls[0] - walls[1], warm_wall_s=walls[1])
        if mesh > 1:
            shards = {s: dict(launches=st.launches,
                              device=int(mux.shards.devices[s].id))
                      for s, st in snap.shards.items()}
            fields["shards"] = shards
            worked = {v["device"] for v in shards.values() if v["launches"]}
            if len(worked) < mesh:
                fail(f"mesh{mesh}: only devices {sorted(worked)} did work")
        report(f"slot mesh={mesh}", **fields)
    diff = max(rel_err(a, b) for a, b in zip(outs[4], outs[1]))
    report("mesh4 vs mesh1", max_rel_diff=diff, tol=TOL_MESH)
    if diff > TOL_MESH:
        fail(f"mesh=4 outputs differ from mesh=1 by {diff:.3e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the slot phase, mesh_size=4 against "
                         "mesh_size=1 (needs four chips)")
    args = ap.parse_args(argv)

    import_repro()
    from repro.launch.xla_env import setup_compile_cache
    setup_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX's default device is {dev.platform!r}, not a TPU")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices; "
             f"JAX sees {len(devices)}")
    from repro.kernels.common import interpret_default
    try:
        interpret = interpret_default()
    except RuntimeError as e:            # interpret mode asked for on a TPU
        fail(str(e))
    if interpret:
        fail("Pallas kernels would run in interpret mode")
    from repro import kernels as K
    print(f"device: {dev.device_kind} x{len(devices)}, jax "
          f"{jax.__version__}", flush=True)

    if args.chips == 4:
        phase_mesh(K, args.seed)
    else:
        phase_slot(K, args.seed)
        phase_large(K, args.seed)
        phase_dag(K, args.seed)
        phase_decode(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
