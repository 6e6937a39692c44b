"""Mixed-traffic solver serving launcher: replay a PUSCH-style trace
through the registry-driven SolverMux and report SLO metrics.

A 5G PUSCH receiver processes traffic in TTI slots; each slot carries a
mix of per-subcarrier-group MMSE equalizations (the bulk), plus control-
path Cholesky solves (noise-covariance whitening) and QR least squares
(channel estimation refits), at several antenna/user sizes.  This
launcher synthesizes that trace on a virtual clock — every job carries a
priority class (control-path solves and half the MMSE bulk are
``hard``-deadline; the rest is ``best_effort`` refinement traffic) —
submits each slot's jobs with a per-slot deadline, ``poll``s the mux
once per slot (full lane groups dispatch immediately; partials wait for
deadline / age / pressure), drains at the end, checks a sample of
results against the registry oracles, and prints per-pipeline p50/p99
latency (overall and per priority), throughput, lane utilization,
padded-lane waste, and — with ``--policy`` — the overload counters
(dropped / preempted / coalesced) and hard-deadline SLO attainment.

  PYTHONPATH=src python -m repro.launch.serve_solvers \
      --slots 8 --lanes 8 --deadline-ms 2.0 --policy

Two helpers here are shared infrastructure rather than CLI plumbing:

* :func:`run_overload` — the deterministic synthetic overload scenario
  (offered load >= 2x lane capacity, mixed priorities, virtual clock)
  behind the SLO-attainment acceptance test.
* :func:`replay_trace` / :func:`load_trace` — replay a committed JSON
  trace (each entry a seed-keyed job, never raw arrays) through a mux on
  a virtual clock, returning the mux so callers can assert on its
  ``events`` decision log (the golden trace-replay regression test).
* :func:`run_chaos` — the seeded chaos-replay scenario (committed fault
  trace + mesh of lane shards) behind ``run_slo``'s ``serve_slo/faults``
  rows and the fault-tolerance acceptance test: launch failures, NaN
  lanes, and a blackholed shard injected into the mixed-priority trace,
  with the supervision/quarantine/demotion observables summarized.

Chaos flags: ``--fault-trace tests/data/fault_trace.json`` attaches a
seeded :class:`~repro.serve.faults.FaultInjector` to the TTI replay;
``--chaos`` runs the canonical chaos scenario instead (requires
``--fault-trace``; ``--fault-seed`` overrides the trace seed).
"""
from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

# launcher module: 8 virtual CPU devices on a CPU-only run (merged into
# XLA_FLAGS before the first jax import; an explicit device count in the
# env wins) so --mesh N and run_sharded_overload work standalone there
from repro.launch.xla_env import force_host_device_count, setup_compile_cache

force_host_device_count(8)

from repro import kernels as K
from repro.kernels.common import sample_spd
from repro.serve import CostModel, ManualClock, OverloadPolicy, SolverMux

SLOT_MS = 0.5          # 5G numerology-1 TTI


def job_args(pipeline: str, n: int, k: int, seed: int) -> tuple:
    """Deterministic per-job problem arrays, keyed by seed — the form
    committed traces store jobs in (never raw arrays)."""
    rng = np.random.default_rng(seed)
    if pipeline == "cholesky_solve":
        return (sample_spd(rng, 1, n)[0],
                rng.standard_normal((n, k)).astype(np.float32))
    m = n + 4
    return (rng.standard_normal((m, n)).astype(np.float32),
            rng.standard_normal((m, k)).astype(np.float32))


def hard_attainment(jobs) -> float:
    """Fraction of hard-deadline jobs that finished by their deadline
    (dropped or late = miss).  NaN when the trace has no hard jobs."""
    hard = [j for j in jobs
            if j.priority == "hard" and j.deadline is not None]
    if not hard:
        return math.nan
    met = sum(1 for j in hard
              if j.state == "done" and j.finished_at <= j.deadline)
    return met / len(hard)


def build_slot_jobs(rng, slot: int, sizes: list[int]):
    """One TTI's job mix: (pipeline, args, priority) tuples.  Alternate
    MMSE jobs arrive as SPLIT re/im planes (the form a real front end
    produces) — the mux routes their 4-arg buckets to the split_complex
    variant.  Control-path solves and the even MMSE groups are hard-
    deadline; odd MMSE groups are best-effort refinement passes."""
    jobs = []
    for n in sizes:
        m = n + 4
        # MMSE bulk: a few subcarrier groups per size per slot
        for i in range(2 + slot % 2):
            priority = "hard" if i % 2 == 0 else "best_effort"
            if i % 2:
                jobs.append(("mmse_equalize", (
                    rng.standard_normal((m, n)).astype(np.float32),
                    rng.standard_normal((m, n)).astype(np.float32),
                    rng.standard_normal((m, 2)).astype(np.float32),
                    rng.standard_normal((m, 2)).astype(np.float32)),
                    priority))
            else:
                h = rng.standard_normal((m, n)).astype(np.float32)
                y = rng.standard_normal((m, 2)).astype(np.float32)
                jobs.append(("mmse_equalize", (h, y), priority))
        # control path: whitening solve + channel refit, not every slot
        if slot % 2 == 0:
            a = sample_spd(rng, 1, n)[0]
            b = rng.standard_normal((n, 2)).astype(np.float32)
            jobs.append(("cholesky_solve", (a, b), "hard"))
        if slot % 3 == 0:
            qa = rng.standard_normal((m, n)).astype(np.float32)
            qb = rng.standard_normal((m, 1)).astype(np.float32)
            jobs.append(("qr_solve", (qa, qb), "hard"))
    return jobs


# ---------------- committed-trace replay (golden tests) ----------------

def load_trace(path: str) -> list[dict]:
    """A committed trace: a JSON list of job entries
    ``{"tick", "pipeline", "n", "k", "priority", "deadline_ticks",
    "seed"}`` — ``deadline_ticks`` null means no deadline."""
    with open(path) as f:
        return json.load(f)


def replay_trace(trace: list[dict], *, lanes: int = 4, tick: float = 1.0,
                 policy: OverloadPolicy | None = None,
                 max_wait: float | None = None,
                 pressure: int | None = None,
                 drain_ticks: int = 2) -> SolverMux:
    """Replay a committed trace on a virtual clock: submit each tick's
    jobs, ``poll`` once per tick, keep polling ``drain_ticks`` empty
    ticks, then ``run()``.  Returns the mux — its ``events`` list is the
    exact flush/drop/preempt/coalesce decision sequence a golden file
    pins."""
    clock = ManualClock()
    mux = SolverMux(lanes=lanes, max_wait=max_wait, pressure=pressure,
                    clock=clock, policy=policy)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(int(entry["tick"]), []).append(entry)
    last = max(by_tick) if by_tick else -1
    for t in range(last + 1 + drain_ticks):
        for e in by_tick.get(t, ()):
            deadline = e.get("deadline_ticks")
            mux.submit(e["pipeline"],
                       *job_args(e["pipeline"], e["n"], e["k"], e["seed"]),
                       deadline=(None if deadline is None
                                 else clock() + deadline * tick),
                       priority=e.get("priority", "best_effort"))
        mux.poll()
        clock.advance(tick)
    mux.run()
    return mux


# ---------------- synthetic overload scenario (bench + tests) ----------

OVERLOAD_TICK = 1.0


def overload_trace(ticks: int, lanes: int, seed: int = 0) -> list[dict]:
    """Synthetic overload: per tick, ``3.5 * lanes`` jobs arrive against
    a budget of ~2 launches = ``2 * lanes`` job-slots — offered load
    well over 2x lane capacity in launch terms (the hard MMSE chunk, two
    best-effort MMSE chunks, and the partial Cholesky buckets each need
    their own launch).  The mix:

      * ``lanes`` hard MMSE bulk (deadline 3 ticks) — the traffic the
        SLO is judged by,
      * ``2 * lanes`` best-effort MMSE refinement with a tight 1.2-tick
        deadline: under EDF admission these outrank the hard chunks
        (earlier deadlines) until preemption steps in, and once expired
        they are dead weight unless shed,
      * 1 hard n=12 Cholesky whitening solve (deadline 2 ticks) — a
        chronically partial bucket, and
      * 1 best-effort n=8 Cholesky solve (deadline 2 ticks) — the
        coalescing donor that can ride the n=12 partials' free lanes.
    """
    trace, seq = [], 0
    for t in range(ticks):
        for i in range(lanes):
            trace.append(dict(tick=t, pipeline="mmse_equalize", n=8, k=2,
                              priority="hard", deadline_ticks=3.0,
                              seed=seed * 100003 + seq)); seq += 1
        for i in range(2 * lanes):
            trace.append(dict(tick=t, pipeline="mmse_equalize", n=8, k=2,
                              priority="best_effort", deadline_ticks=1.2,
                              seed=seed * 100003 + seq)); seq += 1
        trace.append(dict(tick=t, pipeline="cholesky_solve", n=12,
                          k=2, priority="hard", deadline_ticks=2.0,
                          seed=seed * 100003 + seq)); seq += 1
        trace.append(dict(tick=t, pipeline="cholesky_solve", n=8,
                          k=2, priority="best_effort",
                          deadline_ticks=2.0,
                          seed=seed * 100003 + seq)); seq += 1
    return trace


def run_overload(policy: bool, *, ticks: int = 8, lanes: int = 4,
                 seed: int = 0, adaptive: bool = False) -> dict:
    """Run the synthetic overload trace with the SAME lane-time budget
    in both modes; ``policy=True`` additionally enables shedding,
    preemption, and coalescing.  Returns the summary the SLO benchmark
    emits and the acceptance test asserts on.

    ``adaptive=True`` runs the cost model with online calibration ON
    (real wall-clock measurements feed :meth:`CostModel.observe`) and
    adds the drift-observability fields (``drift`` /
    ``calibration_updates``) to the summary — the source of the
    ``serve_slo/drift/*`` rows in the persisted bench baseline."""
    cm = CostModel(adaptive=adaptive)
    spec = K.get("mmse_equalize")
    unit = cm.launch_cost("mmse_equalize", spec.base,
                          ((12, 8), (12, 2)), lanes)
    pol = OverloadPolicy(shed=policy, preempt=policy, coalesce=policy,
                         budget=2.0 * unit, cost_model=cm)
    trace = overload_trace(ticks, lanes, seed)
    jobs, clock = [], ManualClock()
    mux = SolverMux(lanes=lanes, clock=clock, pressure=2 * lanes,
                    policy=pol)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(entry["tick"], []).append(entry)
    for t in range(ticks + ticks):        # arrival ticks + drain ticks
        for e in by_tick.get(t, ()):
            jobs.append(mux.submit(
                e["pipeline"],
                *job_args(e["pipeline"], e["n"], e["k"], e["seed"]),
                deadline=clock() + e["deadline_ticks"] * OVERLOAD_TICK,
                priority=e["priority"]))
        mux.poll()
        clock.advance(OVERLOAD_TICK)
    mux.run()
    snap = mux.metrics()
    summary = {
        "policy": policy,
        "jobs": len(jobs),
        "done": sum(1 for j in jobs if j.state == "done"),
        "attainment_hard": hard_attainment(jobs),
        "dropped": snap.total_dropped,
        "hard_dropped": sum(1 for j in jobs
                            if j.priority == "hard"
                            and j.state == "dropped"),
        "preempted": snap.total_preempted,
        "coalesced": snap.total_coalesced,
        "launches": snap.total_launches,
    }
    if adaptive:
        summary["drift"] = {
            key: {"ratio": st.ratio, "updates": st.updates,
                  "source": st.source, "alert": st.alert}
            for key, st in snap.drift.items() if st.updates > 0}
        summary["calibration_updates"] = snap.calibration_updates
    return summary


def run_sharded_overload(mesh_size: int, *, ticks: int = 6,
                         lanes: int = 4, load_lanes: int | None = None,
                         seed: int = 0) -> dict:
    """Virtual-clock replay of the committed overload trace against a
    mesh of ``mesh_size`` lane shards — the scaling scenario the
    sharded-serving tests compare across mesh sizes.

    The offered load is generated for ``load_lanes`` lanes (default
    ``8 * lanes`` — saturating even the largest swept mesh) and replayed
    over a FIXED virtual window of ``2 * ticks`` one-tick polls with NO
    final drain, so ``throughput`` measures steady-state capacity at
    this mesh size, not how fast a drain call empties the queue.  Every
    mesh size sees the identical trace and window; only the lane-pool
    capacity (``lanes * mesh_size``) changes.

    Returns the summary the benchmark emits: aggregate job throughput
    (jobs per virtual tick), hard-SLO attainment, launch counts (total
    and mesh-spanning), per-shard lane utilization, and the measured
    per-(pipeline, variant, mesh) calibration rows ``from_bench_json``
    re-fits shard overheads from."""
    if load_lanes is None:
        load_lanes = 8 * lanes
    cm = CostModel()
    spec = K.get("mmse_equalize")
    unit = cm.launch_cost("mmse_equalize", spec.base,
                          ((12, 8), (12, 2)), lanes)
    pol = OverloadPolicy(budget=2.0 * mesh_size * unit, cost_model=cm)
    trace = overload_trace(ticks, load_lanes, seed)
    jobs, clock = [], ManualClock()
    mux = SolverMux(lanes=lanes, clock=clock, pressure=2 * lanes,
                    policy=pol, mesh_size=mesh_size)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(entry["tick"], []).append(entry)
    for t in range(ticks + ticks):        # arrival ticks + drain ticks
        for e in by_tick.get(t, ()):
            jobs.append(mux.submit(
                e["pipeline"],
                *job_args(e["pipeline"], e["n"], e["k"], e["seed"]),
                deadline=clock() + e["deadline_ticks"] * OVERLOAD_TICK,
                priority=e["priority"]))
        mux.poll()
        clock.advance(OVERLOAD_TICK)
    # NO mux.run(): the window is fixed, so throughput compares capacity
    window = 2 * ticks * OVERLOAD_TICK
    snap = mux.metrics()
    done = sum(1 for j in jobs if j.state == "done")
    spanning = sum(1 for l in snap.launches if l.mesh > 1)
    if snap.shards:
        shard_util = {s: st.utilization for s, st in snap.shards.items()}
    else:
        real = sum(l.real for l in snap.launches)
        width = sum(l.real + l.padded for l in snap.launches)
        shard_util = {0: (real / width) if width else 0.0}
    calibration = []
    by_pvm: dict[tuple, list] = {}
    for l in snap.launches:
        if not math.isnan(l.measured):
            by_pvm.setdefault((l.pipeline, l.variant, l.mesh),
                              []).append(l)
    for (pipeline, vname, mesh), recs in sorted(by_pvm.items()):
        pspec = K.get(pipeline)
        variant = pspec.base if vname == "base" else \
            next(v for v in pspec.variants if v.name == vname)
        shapes = tuple(tuple(shape) for shape, _ in recs[0].shape)
        walls = sorted(l.measured for l in recs)
        calibration.append({
            "pipeline": pipeline, "variant": vname, "mesh": mesh,
            "lanes": recs[0].real + recs[0].padded,
            "wall_us": walls[len(walls) // 2] * 1e6,
            "model_flops": variant.model_flops(shapes),
        })
    return {
        "mesh": mesh_size,
        "jobs": len(jobs),
        "done": done,
        "throughput": done / window,
        "attainment_hard": hard_attainment(jobs),
        "dropped": snap.total_dropped,
        "launches": snap.total_launches,
        "spanning": spanning,
        "shard_util": shard_util,
        "imbalance": snap.shard_imbalance,
        "pending": mux.pending(),
        "calibration": calibration,
    }


# ---------------- seeded chaos replay (faults bench + tests) ----------

def chaos_trace(ticks: int, lanes: int, seed: int = 0) -> list[dict]:
    """The canonical chaos workload: per tick, ``lanes`` hard MMSE
    equalizations (deadline 3 ticks — the SLO traffic), ``lanes``
    best-effort MMSE refinements (deadline 2 ticks), and one hard n=128
    Cholesky whitening solve (deadline 3 ticks) whose bucket dispatches
    to the *blocked* variant — the target the committed fault trace
    shoots at to force a variant demotion."""
    trace, seq = [], 0
    for t in range(ticks):
        for i in range(lanes):
            trace.append(dict(tick=t, pipeline="mmse_equalize", n=8, k=2,
                              priority="hard", deadline_ticks=3.0,
                              seed=seed * 100003 + seq)); seq += 1
        for i in range(lanes):
            trace.append(dict(tick=t, pipeline="mmse_equalize", n=8, k=2,
                              priority="best_effort", deadline_ticks=2.0,
                              seed=seed * 100003 + seq)); seq += 1
        trace.append(dict(tick=t, pipeline="cholesky_solve", n=128,
                          k=2, priority="hard", deadline_ticks=3.0,
                          seed=seed * 100003 + seq)); seq += 1
    return trace


def run_chaos(fault_trace: str | dict | None, *, mesh_size: int = 4,
              ticks: int = 10, lanes: int = 2, seed: int = 0,
              fault_seed: int = 0) -> dict:
    """Replay the chaos workload against a ``mesh_size`` lane mesh with
    the given fault trace injected (``None``: the fault-free reference
    run the attainment ratio is judged against).  Deterministic end to
    end — virtual clock, seed-keyed jobs, seed-keyed faults — so the
    event stream is golden-file-pinnable.

    Returns the summary the ``--chaos`` run prints: hard-SLO attainment,
    per-state job counts, ``hard_lost`` (hard jobs left in no terminal
    state, or failed without a structured reason — must be zero), the
    supervision observables (retries / failed jobs / quarantines /
    reinstatements / demotions), and the drained event stream."""
    import os

    from repro.serve import FaultInjector
    if fault_trace is None:
        injector = None
    elif isinstance(fault_trace, (str, os.PathLike)):
        injector = FaultInjector.from_json(fault_trace, seed=fault_seed)
    else:
        injector = FaultInjector(fault_trace, seed=fault_seed)
    pol = OverloadPolicy(budget=None, cost_model=CostModel())
    trace = chaos_trace(ticks, lanes, seed)
    jobs, clock = [], ManualClock()
    mux = SolverMux(lanes=lanes, clock=clock, pressure=2 * lanes,
                    policy=pol, mesh_size=mesh_size, injector=injector)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(entry["tick"], []).append(entry)
    for t in range(ticks + ticks):        # arrival ticks + drain ticks
        for e in by_tick.get(t, ()):
            jobs.append(mux.submit(
                e["pipeline"],
                *job_args(e["pipeline"], e["n"], e["k"], e["seed"]),
                deadline=clock() + e["deadline_ticks"] * OVERLOAD_TICK,
                priority=e["priority"]))
        mux.poll()
        clock.advance(OVERLOAD_TICK)
    mux.run()
    snap = mux.metrics()
    hard = [j for j in jobs if j.priority == "hard"]
    return {
        "faulted": injector is not None,
        "mesh": mesh_size,
        "jobs": len(jobs),
        "done": sum(1 for j in jobs if j.state == "done"),
        "failed": sum(1 for j in jobs if j.state == "failed"),
        "dropped": snap.total_dropped,
        "hard_failed": sum(1 for j in hard if j.state == "failed"),
        # a hard job is LOST iff it reached no terminal state or failed
        # without a structured reason — the acceptance gate is zero
        "hard_lost": sum(1 for j in hard
                         if j.state not in ("done", "failed", "dropped")
                         or (j.state == "failed" and not j.reason)),
        "attainment_hard": hard_attainment(jobs),
        "retries": snap.faults.retries,
        "failed_jobs": snap.faults.failed_jobs,
        "quarantines": snap.faults.quarantines,
        "reinstatements": snap.faults.reinstatements,
        "demotions": snap.faults.demotions,
        "time_to_recover": snap.faults.time_to_recover,
        "alerts": list(snap.faults.alerts),
        "pending": mux.pending(),
        "events": mux.drain_events(),
    }


# ---------------- served PUSCH DAG (bench + golden tests) -------------

def dag_job_args(dag: str, n: int, seed: int) -> tuple:
    """Deterministic per-DAG-job problem arrays, keyed by seed — the
    form committed DAG traces store jobs in (never raw arrays)."""
    return K.get_dag(dag).make_case(np.random.default_rng(seed), n)


def pusch_trace(ticks: int, seed: int = 0, *,
                chained: bool = False) -> list[dict]:
    """The canonical served-DAG workload: one hard ``pusch_receive``
    DAG per tick plus one best-effort ``svd_solve`` DAG every other
    tick (the generality traffic).  The PUSCH deadlines are *staggered
    to the same absolute tick* in pairs (tick t gets ``8 - t % 2``
    ticks), so consecutive DAGs compete at EQUAL deadline while sitting
    at different stages — the window where criticality-first admission
    is observable: the later DAG's critical channel-estimate stage must
    flush ahead of the earlier DAG's slack equalize stage (plain
    FIFO/seq order would invert that), which the golden event stream
    pins."""
    trace, seq = [], 0
    for t in range(ticks):
        trace.append(dict(tick=t, dag="pusch_receive", n=8,
                          priority="hard",
                          deadline_ticks=8.0 - t % 2,
                          chained=chained,
                          seed=seed * 100003 + seq)); seq += 1
        if t % 2 == 0:
            trace.append(dict(tick=t, dag="svd_solve", n=8,
                              priority="best_effort",
                              deadline_ticks=12.0, chained=False,
                              seed=seed * 100003 + seq)); seq += 1
    return trace


def replay_pusch(trace: list[dict], *, lanes: int = 4, tick: float = 1.0,
                 drain_ticks: int = 6, injector=None,
                 mesh_size: int | None = None):
    """Replay a committed DAG trace on a virtual clock: submit each
    tick's DAGs, ``poll`` once per tick (each poll serves the ready
    stage frontier and advances the DAGs), keep polling ``drain_ticks``
    empty ticks, then ``run()``.  Returns ``(mux, dag_jobs)`` — the
    mux's ``events`` list is the stage-scheduling decision sequence the
    golden file pins."""
    clock = ManualClock()
    mux = SolverMux(lanes=lanes, max_wait=0.0, clock=clock,
                    policy=OverloadPolicy(budget=None,
                                          cost_model=CostModel()),
                    mesh_size=mesh_size, injector=injector)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(int(entry["tick"]), []).append(entry)
    last = max(by_tick) if by_tick else -1
    dags = []
    for t in range(last + 1 + drain_ticks):
        for e in by_tick.get(t, ()):
            deadline = e.get("deadline_ticks")
            dags.append(mux.submit_dag(
                e["dag"], *dag_job_args(e["dag"], e["n"], e["seed"]),
                deadline=(None if deadline is None
                          else clock() + deadline * tick),
                priority=e.get("priority", "best_effort"),
                chained=e.get("chained", False)))
        mux.poll()
        clock.advance(tick)
    mux.run()
    return mux, dags


def dag_hard_lost(dags) -> int:
    """Hard DAGs (or their stages) left unaccounted: a hard DAG is LOST
    iff it reached no terminal state, or any submitted stage job is
    neither terminal nor explicitly cancelled — the acceptance gate is
    zero (a mid-DAG fault must cascade cleanly, never orphan)."""
    lost = 0
    for d in dags:
        if d.priority != "hard":
            continue
        if d.state not in ("done", "failed", "dropped"):
            lost += 1
            continue
        for stage in d.spec.stage_list(chained=d.chained):
            sj = d.stages.get(stage.name)
            if sj == "cancelled":
                continue
            if sj is None or sj.state not in ("done", "failed",
                                              "dropped"):
                lost += 1
                break
    return lost


def run_pusch(chained: bool, *, ticks: int = 4, lanes: int = 4,
              seed: int = 0, fault_trace: str | dict | None = None,
              fault_seed: int = 0) -> dict:
    """Run the canonical PUSCH DAG trace end to end — stage-independent
    (``chained=False``: FFT -> channel-estimate -> equalize as three
    launches with buffer handoffs) or stage-chained (``chained=True``:
    the channel-estimate->equalize tail fused lane-resident in one
    ``pallas_call``) — and summarize the end-to-end SLO view the
    ``serve_slo/dag/*`` benchmark rows gate: e2e p50/p99 latency in
    virtual ticks, launch counts, and (under an injected fault trace)
    the containment observables with ``hard_lost`` required zero."""
    import os

    from repro.serve import FaultInjector
    if fault_trace is None:
        injector = None
    elif isinstance(fault_trace, (str, os.PathLike)):
        injector = FaultInjector.from_json(fault_trace, seed=fault_seed)
    else:
        injector = FaultInjector(fault_trace, seed=fault_seed)
    trace = pusch_trace(ticks, seed, chained=chained)
    mux, dags = replay_pusch(trace, lanes=lanes, injector=injector)
    snap = mux.metrics()
    pstats = snap.dags.get("pusch_receive")
    pusch = [d for d in dags if d.dag == "pusch_receive"]
    return {
        "chained": chained,
        "faulted": injector is not None,
        "dags": len(dags),
        "pusch_dags": len(pusch),
        "done": sum(1 for d in dags if d.state == "done"),
        "failed": sum(1 for d in dags if d.state == "failed"),
        "dropped": sum(1 for d in dags if d.state == "dropped"),
        "hard_lost": dag_hard_lost(dags),
        "e2e_p50": pstats.latency.p50 if pstats else math.nan,
        "e2e_p99": pstats.latency.p99 if pstats else math.nan,
        "launches": snap.total_launches,
        "retries": snap.faults.retries,
        "failed_jobs": snap.faults.failed_jobs,
        "pending": mux.pending(),
        "events": mux.drain_events(),
    }


# ---------------- mixed solver + decode traffic ----------------

_DECODE_MODEL = None


def decode_model():
    """The smoke-scale LM ``(cfg, params)`` shared by every decode
    scenario in this launcher — deterministic (fixed init key) and
    built once per process (transformer init is the expensive part)."""
    global _DECODE_MODEL
    if _DECODE_MODEL is None:
        import jax

        from repro.configs import get_smoke
        from repro.models import transformer as T
        cfg = get_smoke("phi4-mini-3.8b")
        _DECODE_MODEL = (cfg, T.init_params(jax.random.key(0), cfg))
    return _DECODE_MODEL


def decode_prompt(length: int, seed: int) -> list[int]:
    """Deterministic seed-keyed prompt tokens — the form committed
    decode traces store prompts in (never raw token arrays)."""
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(2, 500, size=length)]


def decode_trace(ticks: int, seed: int = 0) -> list[dict]:
    """The canonical mixed solver+decode workload: per tick, one hard
    and one best-effort MMSE bulk chunk (solver lane traffic) plus two
    decode requests — one hard greedy, one best-effort (periodically
    sampled) — with prompt/output lengths that VARY per tick.  The
    heterogeneity is the point: lockstep pool decode runs every pool
    member to the longest prompt and longest ``max_new`` of its
    generation and rebuilds the cache between pools, so on this trace
    continuous per-slot batching strictly beats it in tokens per SPMD
    step at the same budget — the acceptance gate the committed
    ``serve_slo/decode/*`` rows pin."""
    trace, seq = [], 0
    for t in range(ticks):
        for i in range(2):
            trace.append(dict(
                tick=t, kind="solve", pipeline="mmse_equalize", n=8, k=2,
                priority="hard" if i == 0 else "best_effort",
                deadline_ticks=3.0, seed=seed * 100003 + seq))
            seq += 1
        trace.append(dict(
            tick=t, kind="decode", prompt_len=1 + t % 4,
            max_new=2 + (3 * t) % 7, temperature=0.0, priority="hard",
            deadline_ticks=8.0, seed=seed * 100003 + seq))
        seq += 1
        trace.append(dict(
            tick=t, kind="decode", prompt_len=1 + (t * 2) % 5,
            max_new=1 + (t * 5) % 9,
            temperature=1.0 if t % 3 == 0 else 0.0,
            priority="best_effort", deadline_ticks=12.0,
            seed=seed * 100003 + seq))
        seq += 1
    return trace


def replay_decode(trace: list[dict], *, lanes: int = 4,
                  slots: int | None = None, max_len: int = 64,
                  tick: float = 1.0, drain_ticks: int = 4,
                  lockstep: bool = False):
    """Replay a committed mixed solver+decode trace on a virtual clock:
    submit each tick's solver jobs and decode requests, ``poll`` once
    per tick (the attached policy round serves solver flushes AND up to
    ``decode_steps_per_poll`` continuous-batching decode steps), keep
    polling ``drain_ticks`` empty ticks, then ``run()``.  Returns
    ``(mux, engine, requests, jobs)`` — the mux's event list interleaves
    solver flush decisions with decode insert/step/done decisions, the
    sequence ``tests/data/decode_golden.json`` pins byte-for-byte.

    The replay engine uses ``eos_id=-1`` (token ids are non-negative,
    so EOS never fires): every request runs exactly ``max_new`` steps
    and the scheduling decision sequence depends only on the trace's
    lengths — never on model floating point — keeping the golden file
    platform-independent.  (EOS semantics are pinned separately by the
    unit suite.)

    ``lockstep=True`` is the equal-budget baseline: the SAME trace,
    clock, mux and solver path, but the engine is NOT attached — decode
    requests go straight to its FIFO and each tick runs one lockstep
    pool drain (:meth:`~repro.serve.decode.DecodeEngine.run_lockstep`)
    instead of continuous steps."""
    from repro.serve import global_config
    from repro.serve.decode import DecodeEngine, Request
    cfg, params = decode_model()
    clock = ManualClock()
    slots = global_config.decode_slots if slots is None else slots
    engine = DecodeEngine(cfg, params, batch=slots, max_len=max_len,
                          eos_id=-1, clock=clock)
    mux = SolverMux(lanes=lanes, max_wait=0.0, clock=clock,
                    policy=OverloadPolicy(budget=None,
                                          cost_model=CostModel()))
    if not lockstep:
        mux.attach_decode(engine)
    by_tick: dict[int, list[dict]] = {}
    for entry in trace:
        by_tick.setdefault(int(entry["tick"]), []).append(entry)
    last = max(by_tick) if by_tick else -1
    requests, jobs = [], []
    for t in range(last + 1 + drain_ticks):
        for e in by_tick.get(t, ()):
            deadline = e.get("deadline_ticks")
            deadline = None if deadline is None \
                else clock() + deadline * tick
            if e.get("kind") == "decode":
                r = Request(
                    prompt=decode_prompt(e["prompt_len"], e["seed"]),
                    max_new=e["max_new"],
                    temperature=e.get("temperature", 0.0))
                if lockstep:
                    r.priority = e.get("priority", "best_effort")
                    r.deadline = deadline
                    engine.submit(r)
                else:
                    mux.submit_decode(
                        r, deadline=deadline,
                        priority=e.get("priority", "best_effort"))
                requests.append(r)
            else:
                jobs.append(mux.submit(
                    e["pipeline"],
                    *job_args(e["pipeline"], e["n"], e["k"], e["seed"]),
                    deadline=deadline,
                    priority=e.get("priority", "best_effort")))
        mux.poll()
        if lockstep:
            engine.run_lockstep()
        clock.advance(tick)
    mux.run()
    if lockstep:
        engine.run_lockstep()
    return mux, engine, requests, jobs


def run_decode_serve(continuous: bool, *, ticks: int = 6, lanes: int = 4,
                     seed: int = 0) -> dict:
    """Run the canonical mixed solver+decode trace end to end —
    continuous per-slot batching through the mux (``continuous=True``)
    or the preserved lockstep pool baseline at the same budget — and
    summarize the view the ``serve_slo/decode/*`` benchmark rows gate:
    tokens per SPMD step (the throughput the continuous path must
    strictly win), per-phase latency, slot reuses, and ``hard_lost``
    (hard solver jobs not done + hard decode requests not finished)
    required zero."""
    trace = decode_trace(ticks, seed)
    mux, engine, requests, jobs = replay_decode(trace, lanes=lanes,
                                                lockstep=not continuous)
    snap = mux.metrics() if continuous else engine.metrics()
    d = snap.decode
    tokens = sum(len(r.out) for r in requests)
    steps = engine.steps
    hard_lost = sum(1 for r in requests
                    if r.priority == "hard" and not r.done)
    hard_lost += sum(1 for j in jobs
                     if j.priority == "hard" and j.state != "done")
    return {
        "continuous": continuous,
        "requests": len(requests),
        "done": sum(1 for r in requests if r.done),
        "dropped": sum(1 for r in requests if r.dropped),
        "tokens": tokens,
        "steps": steps,
        "tokens_per_step": tokens / steps if steps else math.nan,
        "hard_lost": hard_lost,
        "solver_jobs": len(jobs),
        "solver_done": sum(1 for j in jobs if j.state == "done"),
        "slot_reuses": d.slot_reuses,
        "insert_p50": d.insert.p50,
        "prefill_p50": d.prefill.p50,
        "generate_p50": d.generate.p50,
        "pending": mux.pending(),
        "events": mux.drain_events(),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8,
                    help="trace length in TTI slots")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--sizes", default="8,12",
                    help="comma-separated antenna sizes n (m = n + 4)")
    ap.add_argument("--deadline-ms", type=float, default=2.0,
                    help="per-job deadline after arrival (virtual ms)")
    ap.add_argument("--max-wait-ms", type=float, default=1.0,
                    help="partial-bucket age flush threshold (virtual ms)")
    ap.add_argument("--policy", action="store_true",
                    help="enable the overload policy: shed expired "
                         "best-effort jobs and coalesce small ones; add "
                         "--budget-us for budgeted admission, which is "
                         "what makes preemption possible")
    ap.add_argument("--budget-us", type=float, default=None,
                    help="per-poll lane-time budget in cost-model "
                         "microseconds (requires --policy)")
    ap.add_argument("--adapt", action="store_true",
                    help="close the cost-model loop online: measure "
                         "every launch, re-fit sec/FLOP + overhead, tune "
                         "flush thresholds from observed traffic, and "
                         "report drift (predicted/measured) per variant")
    ap.add_argument("--mesh", type=int, default=None,
                    help="lane-shard count: span each pool's lane axis "
                         "over this many local devices (needs "
                         "--xla_force_host_platform_device_count or "
                         "real devices; default REPRO_SERVE_MESH_SIZE)")
    ap.add_argument("--fault-trace", default=None,
                    help="JSON fault trace (see repro.serve.faults) to "
                         "inject into the replay via a seeded "
                         "FaultInjector")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="seed for the fault injector's per-attempt rng "
                         "streams (requires --fault-trace; a seed in "
                         "the trace file wins)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the canonical chaos scenario (mesh=4 "
                         "lane shards, mixed-priority trace, the fault "
                         "trace injected) instead of the TTI replay and "
                         "print the supervision observables (requires "
                         "--fault-trace)")
    ap.add_argument("--pusch", action="store_true",
                    help="serve the canonical PUSCH-receiver DAG trace "
                         "(staged vs stage-chained, criticality-ordered "
                         "admission) instead of the TTI replay and print "
                         "the end-to-end DAG observables; combine with "
                         "--fault-trace for a mid-DAG stage fault")
    ap.add_argument("--decode", action="store_true",
                    help="serve the canonical mixed solver+decode trace "
                         "(continuous per-slot batching through the mux "
                         "vs the lockstep pool baseline at the same "
                         "budget) instead of the TTI replay and print "
                         "the token-throughput observables")
    ap.add_argument("--ticks", type=int, default=4,
                    help="virtual ticks in the --pusch / --decode trace")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()
    if args.budget_us is not None and not args.policy:
        ap.error("--budget-us requires --policy")
    if args.fault_seed is not None and args.fault_trace is None:
        ap.error("--fault-seed requires --fault-trace")
    if args.chaos and args.fault_trace is None:
        ap.error("--chaos requires --fault-trace")
    sizes = [int(s) for s in args.sizes.split(",")]

    if args.pusch:
        staged = run_pusch(False, ticks=args.ticks, lanes=args.lanes,
                           seed=args.seed, fault_trace=args.fault_trace,
                           fault_seed=args.fault_seed or 0)
        chained = run_pusch(True, ticks=args.ticks, lanes=args.lanes,
                            seed=args.seed)
        for s in (staged, chained):
            mode = "chained" if s["chained"] else "staged"
            fault = " +faults" if s["faulted"] else ""
            print(f"pusch dag [{mode}{fault}]: dags={s['dags']} "
                  f"done={s['done']} failed={s['failed']} "
                  f"dropped={s['dropped']} hard_lost={s['hard_lost']}")
            print(f"  e2e latency (ticks): p50={s['e2e_p50']:.1f} "
                  f"p99={s['e2e_p99']:.1f}  launches={s['launches']} "
                  f"retries={s['retries']}")
        if staged["e2e_p50"] and chained["e2e_p50"]:
            print(f"  stage-chained speedup: "
                  f"{staged['e2e_p50'] / chained['e2e_p50']:.2f}x e2e p50")
        assert staged["hard_lost"] == 0, "hard DAGs silently lost"
        assert chained["hard_lost"] == 0, "hard DAGs silently lost"
        return

    if args.chaos:
        summary = run_chaos(args.fault_trace, seed=args.seed,
                            fault_seed=args.fault_seed or 0)
        base = run_chaos(None, seed=args.seed)
        print(f"chaos replay: mesh={summary['mesh']} "
              f"jobs={summary['jobs']} done={summary['done']} "
              f"failed={summary['failed']} dropped={summary['dropped']}")
        print(f"  hard: lost={summary['hard_lost']} "
              f"failed={summary['hard_failed']} "
              f"attainment={summary['attainment_hard']:.2%} "
              f"(fault-free {base['attainment_hard']:.2%})")
        print(f"  supervision: retries={summary['retries']} "
              f"quarantines={summary['quarantines']} "
              f"reinstatements={summary['reinstatements']} "
              f"demotions={summary['demotions']} "
              f"t_recover={summary['time_to_recover']:.2f}")
        for alert in summary["alerts"]:
            print(f"  ALERT {alert}")
        assert summary["hard_lost"] == 0, "hard jobs silently lost"
        return

    if args.decode:
        cont = run_decode_serve(True, ticks=args.ticks,
                                lanes=args.lanes, seed=args.seed)
        base = run_decode_serve(False, ticks=args.ticks,
                                lanes=args.lanes, seed=args.seed)
        for s in (cont, base):
            mode = "continuous" if s["continuous"] else "lockstep"
            print(f"decode serve [{mode:>10}]: requests={s['requests']} "
                  f"done={s['done']} dropped={s['dropped']} "
                  f"tokens={s['tokens']} steps={s['steps']} "
                  f"tokens/step={s['tokens_per_step']:.2f} "
                  f"hard_lost={s['hard_lost']} "
                  f"solver {s['solver_done']}/{s['solver_jobs']}")
        print(f"  continuous: slot_reuses={cont['slot_reuses']} "
              f"insert p50 (ticks)={cont['insert_p50']:.1f} "
              f"prefill p50 (s)={cont['prefill_p50']:.2e} "
              f"generate p50 (s)={cont['generate_p50']:.2e}")
        print(f"  continuous-batching speedup: "
              f"{cont['tokens_per_step'] / base['tokens_per_step']:.2f}x "
              f"tokens/step at equal budget")
        assert cont["hard_lost"] == 0, "hard jobs/requests silently lost"
        assert base["hard_lost"] == 0, "hard jobs/requests silently lost"
        assert cont["tokens"] == base["tokens"], \
            "trace served different token counts across modes"
        assert cont["tokens_per_step"] > base["tokens_per_step"], \
            "continuous batching failed to beat the lockstep baseline"
        return

    rng = np.random.default_rng(args.seed)
    clock = ManualClock()
    policy, cost_model = None, None
    budget = None if args.budget_us is None else args.budget_us * 1e-6
    if args.policy and args.adapt:
        policy = OverloadPolicy(budget=budget,
                                cost_model=CostModel(adaptive=True))
    elif args.policy:
        policy = OverloadPolicy(budget=budget)
    elif args.adapt:
        cost_model = CostModel(adaptive=True)
    injector = None
    if args.fault_trace is not None:
        from repro.serve import FaultInjector
        injector = FaultInjector.from_json(args.fault_trace,
                                           seed=args.fault_seed or 0)
    mux = SolverMux(lanes=args.lanes, max_wait=args.max_wait_ms * 1e-3,
                    clock=clock, policy=policy, cost_model=cost_model,
                    adapt=args.adapt or None, mesh_size=args.mesh,
                    injector=injector)

    t0 = time.perf_counter()
    jobs, done, sample = [], [], None
    for slot in range(args.slots):
        for pipeline, job_arrays, priority in build_slot_jobs(rng, slot,
                                                              sizes):
            job = mux.submit(pipeline, *job_arrays,
                             deadline=clock() + args.deadline_ms * 1e-3,
                             priority=priority)
            jobs.append(job)
            if sample is None and pipeline == "mmse_equalize":
                sample = job
        done.extend(mux.poll())
        clock.advance(SLOT_MS * 1e-3)
    done.extend(mux.run())
    wall = time.perf_counter() - t0
    assert not mux.pending(), "mux left jobs queued after drain"

    if not done:
        print(f"empty trace ({args.slots} slots): nothing served")
        return

    # spot-check a served result against the registry oracle (under
    # fault injection some jobs may be terminally failed — skip those)
    if sample is None or sample.state != "done":
        sample = next((j for j in done if j.state == "done"), None)
    if sample is not None:
        want = K.get(sample.pipeline).run_oracle_lane(*sample.args)
        err = np.max(np.abs(sample.out - want)) \
            / (np.max(np.abs(want)) + 1e-12)
        assert err < 1e-3, \
            f"oracle mismatch on sample job: rel err {err:.2e}"

    snap = mux.metrics()
    print(f"trace: {args.slots} slots x sizes {sizes}, lanes={args.lanes} "
          f"-> {snap.total_jobs} jobs in {snap.total_launches} grid "
          f"launches ({wall:.2f}s wall, oracle check ok)")
    hdr = (f"{'pipeline':<16} {'jobs':>5} {'launch':>6} {'util':>6} "
           f"{'waste':>6} {'p50_ms':>8} {'p99_ms':>8} {'hard_p99':>9} "
           f"{'jobs/s':>10} dispatch")
    print(hdr)
    print("-" * len(hdr))
    for name, st in sorted(snap.pipelines.items()):
        counts = ",".join(f"{v}:{c}" for v, c in
                          sorted(st.dispatch_counts.items()))
        hard = st.latency_by_priority.get("hard")
        hard_p99 = f"{hard.p99 * 1e3:>9.3f}" if hard else f"{'-':>9}"
        print(f"{name:<16} {st.jobs:>5} {st.launches:>6} "
              f"{st.lane_utilization:>6.2f} {st.padded_lane_waste:>6.2f} "
              f"{st.latency.p50 * 1e3:>8.3f} {st.latency.p99 * 1e3:>8.3f} "
              f"{hard_p99} {st.throughput:>10.1f} {counts}")
    missed = sum(1 for j in done
                 if j.deadline is not None and j.finished_at > j.deadline)
    print(f"deadline misses (virtual clock): {missed}/{len(done)}")
    print(f"hard-deadline SLO attainment: {hard_attainment(jobs):.2%}")
    if policy is not None:
        print(f"overload policy: dropped={snap.total_dropped} "
              f"preempted={snap.total_preempted} "
              f"coalesced={snap.total_coalesced}")
    if snap.shards:
        util = " ".join(f"s{s}:{st.utilization:.2f}"
                        for s, st in sorted(snap.shards.items()))
        alert = "  ALERT" if snap.shard_imbalance_alert else ""
        print(f"mesh: {mux.mesh_size} shards, util {util}, "
              f"imbalance {snap.shard_imbalance:.2f}{alert}")
    if snap.drift:
        print("cost-model drift (predicted/measured, EWMA ratio):")
        for key, st in sorted(snap.drift.items()):
            flag = "  ALERT" if st.alert else ""
            print(f"  {key:<28} ratio {st.ratio:>8.3f} "
                  f"updates {st.updates:>4} source {st.source}{flag}")
        worst = snap.worst_drift
        if worst is not None:
            print(f"  worst offender: {worst.key} "
                  f"(ratio {worst.ratio:.3f})")
        ups = ",".join(f"{k}={v}" for k, v in
                       sorted(snap.calibration_updates.items()))
        print(f"  calibration updates: {ups}")


if __name__ == "__main__":
    main()
