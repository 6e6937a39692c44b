"""The benchmark harness: one cell, one run, driven by data.

Everything particular to a cell lives in files found by name:

  BENCHMARK.json              cells, metrics, and each configuration's file
  configs/<config>.json       shapes, mux settings, limits, job kind
  jobs/<kind>.py              inputs from the seed, plain reference, control
  traffic/<mix>.json          loop type, rate or outstanding count, pool size
  metrics/<metric>.py         one reader per metric (or per metric stem,
                              the part of the name before the first ``.``)
  work/<kernel>.py            a kernel's operations and bytes from shapes
  peaks.json                  peaks by ``device_kind``

A run builds a ``SolverMux``, makes a pool of distinct requests from the
seed, warms up the cell's one shape bucket, measures a window with an
open-loop or closed-loop load generator, drains, checks every served answer
against the job kind's reference, and reads its metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad file, ...)."""


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of one cell."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = _json(ROOT, conf["file"])
    traffic = _json(HERE, "traffic", entry["traffic"] + ".json")
    return entry, cfg, traffic


def job_kind(cfg: dict):
    return importlib.import_module(f"chipbench.jobs.{cfg['job_kind']}")


def work(kernel: str):
    return importlib.import_module(f"chipbench.work.{kernel}")


def reader(metric: str):
    """The reader of ``metric``: ``metrics/<metric>.py``, else the reader
    of its stem (``mux_host_ms.rt`` -> ``metrics/mux_host_ms.py``)."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            mod_name = "chipbench_metric_" + stem.replace(".", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise BenchError(f"no reader for metric {metric!r} under metrics/")


def peaks(device_kind: str) -> dict:
    table = _json(HERE, "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def metrics_for(bench: dict, name: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or name in m["workloads"]]


# ---------------------------------------------------------------------------
# Host spans and compile counting
# ---------------------------------------------------------------------------

class Spans:
    """Host-clock totals per span name; with ``trace``, each span is also
    a ``jax.profiler.TraceAnnotation`` in the profiler's trace."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.total: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.total[name] = self.total.get(name, 0.0) \
            + time.perf_counter() - t0


class CompileCounter:
    """Counts JAX tracing and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kwargs):
        if self.on and name in self.EVENTS:
            self.count += 1


# ---------------------------------------------------------------------------
# Requests and load generators
# ---------------------------------------------------------------------------

# The check keeps the answers of the pool's first pass, so that every
# distinct input is compared, and of one later request in CHECK_EVERY,
# drawn from the seed.  A server hands its answers on; keeping every one
# would grow the host's memory through the window (~1.6 GB a run).
CHECK_EVERY = 16


@dataclasses.dataclass(eq=False)
class Request:
    """One request: its jobs until they are all terminal, then only
    their states, finish times and, if ``keep``, answers (so that served
    jobs do not pile up inside the window)."""
    idx: int
    pool_idx: int
    due: float
    submitted: float
    keep: bool
    jobs: list | None
    outs: list | None = None
    states: list | None = None
    fins: list | None = None

    def harvest(self) -> bool:
        """True once every job is terminal (and its record kept)."""
        if self.jobs is None:
            return True
        if any(j.state == "queued" for j in self.jobs):
            return False
        if self.keep:
            self.outs = [j.out for j in self.jobs]
        self.states = [j.state for j in self.jobs]
        self.fins = [j.finished_at for j in self.jobs]
        self.jobs = None
        return True


def build_pool(kind, cfg: dict, seed: int, size: int) -> list[list[tuple]]:
    """``size`` distinct requests, each from its own stream of the seed."""
    return [kind.make_request(cfg, np.random.default_rng([seed, p]))
            for p in range(size)]


def make_mux(cfg: dict):
    from repro.serve import SolverMux
    mux_cfg = cfg["mux"]
    kind = job_kind(cfg)
    return SolverMux(lanes=mux_cfg["lanes"],
                     mesh_size=mux_cfg.get("mesh_size", 1),
                     options={kind.PIPELINE: mux_cfg.get("options", {})})


class LoadGen:
    """Submits requests from the pool to the mux and keeps their record."""

    def __init__(self, mux, kind, cfg: dict, pool: list, spans: Spans,
                 seed: int, clock=time.monotonic):
        self.mux, self.kind, self.cfg, self.pool = mux, kind, cfg, pool
        self.spans = spans
        # the check's own stream; the pool's are [seed, 0 .. pool - 1]
        self.sample = np.random.default_rng([seed, 2 ** 32])
        self.clock = clock
        self.requests: list[Request] = []
        self.live: list[Request] = []

    def submit(self, due: float) -> Request:
        idx = len(self.requests)
        with self.spans("generate"):
            p = idx % len(self.pool)
            args = self.pool[p]
            dl = self.cfg.get("deadline_s")
            deadline = None if dl is None else due + dl
            keep = (idx < len(self.pool)
                    or self.sample.random() < 1.0 / CHECK_EVERY)
        now = self.clock()
        with self.spans("submit"):
            jobs = [self.mux.submit(self.kind.PIPELINE, *a,
                                    deadline=deadline,
                                    priority=self.cfg["priority"])
                    for a in args]
        req = Request(idx, p, due, now, keep, jobs)
        self.requests.append(req)
        self.live.append(req)
        return req

    def poll(self) -> list:
        with self.spans("poll"):
            done = self.mux.poll()
        self.live = [r for r in self.live if not r.harvest()]
        return done

    def drain(self) -> None:
        with self.spans("drain"):
            self.mux.run()
        self.live = [r for r in self.live if not r.harvest()]


def drive_open(d: LoadGen, traffic: dict, t0: float, end: float) -> float:
    """Open loop: request ``i`` is due at ``t0 + i / rate`` and is
    submitted when due, whatever is still queued.  Returns the clock at
    which the window closed; every request due before ``end`` is in."""
    period = 1.0 / float(traffic["rate_per_s"])
    while True:
        now = d.clock()
        if now >= end:
            break
        while t0 + len(d.requests) * period <= now:
            d.submit(t0 + len(d.requests) * period)
        d.poll()
        if d.mux.pending() == 0:
            wake = min(t0 + len(d.requests) * period, end)
            if wake > d.clock():
                with d.spans("wait"):
                    time.sleep(max(0.0, wake - d.clock()))
    closed = d.clock()
    while t0 + len(d.requests) * period < end:
        d.submit(t0 + len(d.requests) * period)
    return closed


def drive_closed(d: LoadGen, traffic: dict, t0: float, end: float) -> float:
    """Closed loop: ``outstanding`` requests are always in flight; the
    next is submitted as soon as one finishes."""
    while d.clock() < end:
        while len(d.live) < int(traffic["outstanding"]):
            d.submit(d.clock())
        if not d.poll():
            d.drain()       # partial buckets with no deadline: flush them
    return d.clock()


LOOPS = {"open": drive_open, "closed": drive_closed}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def rel_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per answer: max |got - want| / max |want| (inf if not finite)."""
    got = np.asarray(got, np.float64)
    axes = tuple(range(1, want.ndim))
    err = np.max(np.abs(got - want), axis=axes) \
        / np.maximum(np.max(np.abs(want), axis=axes), 1e-30)
    return np.where(np.all(np.isfinite(got), axis=axes), err, np.inf)


def check(kind, cfg: dict, pool: list, requests: list[Request]) -> dict:
    """Compare every kept answer with the reference of its input, and
    count the jobs of every request that did not end ``done``."""
    refs = [kind.reference(cfg, args) for args in pool]
    worst, compared, failed, kept = 0.0, 0, 0, 0
    for r in requests:
        if r.outs is None:
            failed += sum(st != "done" for st in r.states)
            continue
        kept += len(r.states)
        done = [i for i, (st, out) in enumerate(zip(r.states, r.outs))
                if st == "done" and out is not None]
        failed += len(r.states) - len(done)
        if not done:
            continue
        want = refs[r.pool_idx][done]
        got = np.stack([r.outs[i] for i in done])
        if got.shape != want.shape:
            return {"max_rel_err": float("inf"), "compared": compared,
                    "kept": kept, "failed": failed + len(done)}
        worst = max(worst, float(np.max(rel_errors(got, want))))
        compared += len(done)
    return {"max_rel_err": worst, "compared": compared, "kept": kept,
            "failed": failed}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "used": devs[:chips]}


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:       # noqa: BLE001 -- backends without stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def warm_up(mux, kind, cfg: dict, pool: list) -> dict:
    """Serve one full launch of the cell's bucket and a padded partial
    one, so that every program the window runs is compiled (or loaded
    from the cache) before it."""
    lanes = cfg["mux"]["lanes"]
    jobs = [a for req in pool for a in req][:lanes + 1]
    served = [mux.submit(kind.PIPELINE, *a, priority=cfg["priority"])
              for a in jobs]
    mux.run()
    bad = [j.state for j in served if j.state != "done"]
    if bad:
        raise BenchError(f"warm-up jobs ended {bad}")
    counts = dict(mux.metrics().pipelines[kind.PIPELINE].dispatch_counts)
    if set(counts) != {cfg["variant"]}:
        raise BenchError(f"the bucket dispatched to {counts}, the "
                         f"configuration states {cfg['variant']!r}")
    mux.reset_metrics()
    mux.drain_events()
    return counts


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, cfg: dict | None = None,
             traffic: dict | None = None) -> dict:
    """One run of one cell; returns the result line's fields plus
    ``info`` (earlier output lines) and ``checks``.  ``cfg`` and
    ``traffic`` replace the cell's files (for tests at small sizes)."""
    import jax
    entry, cfg_file, traffic_file = cell(bench, name)
    cfg = cfg or cfg_file
    traffic = traffic or traffic_file
    kind = job_kind(cfg)
    dev = device_info(entry["chips"])
    counter = CompileCounter()
    pool = build_pool(kind, cfg, seed, int(traffic["pool"]))
    mux = make_mux(cfg)
    warm_up(mux, kind, cfg, pool)
    spans = Spans(trace)
    gen = LoadGen(mux, kind, cfg, pool, spans, seed)
    trace_dir = os.path.join(OUT, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc.collect()
    gc.freeze()         # set-up's objects are never scanned in the window
    counter.on = True
    t0 = gen.clock()
    setup_s = t0 - t_start
    end = t0 + seconds
    ann = jax.profiler.TraceAnnotation("window") if trace \
        else contextlib.nullcontext()
    with ann:
        closed = LOOPS[traffic["loop"]](gen, traffic, t0, end)
    gen.drain()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    snap = mux.metrics()
    peak = memory_peak(dev["used"])
    requests = gen.requests
    del mux
    gc.collect()
    gc.unfreeze()
    verdict = check(kind, cfg, pool, requests)
    attempted = sum(len(r.states) for r in requests)
    done = [r for r in requests if all(st == "done" for st in r.states)]
    # a run that left the stated variant (a demotion) is no sound run
    variants = {v for p in snap.pipelines.values() for v in p.dispatch_counts}
    record = {
        "cell": name, "config": cfg, "traffic": traffic,
        "shapes": kind.shapes(cfg),
        "setup_s": setup_s, "window_s": seconds,
        "requests": len(requests),
        "latencies_s": [max(r.fins) - r.due for r in done],
        "lateness_s": [r.submitted - r.due for r in requests],
        "solves_in_window": sum(1 for r in requests
                                for st, f in zip(r.states, r.fins)
                                if st == "done" and f <= end),
        "span_s": dict(spans.total),
        "launch_s": float(sum(lr.measured for lr in snap.launches)),
        "launches": len(snap.launches),
        # jobs served by the launches that ended inside the window (the
        # traced span), filler lanes left out
        "jobs_launched_in_window": sum(lr.real for lr in snap.launches
                                       if t0 <= lr.t <= closed),
        "device_kind": dev["kind"],
    }
    summary = None
    if trace:
        from chipbench import trace as tr
        path = tr.find_xplane(trace_dir)
        if path is None:
            raise BenchError(f"the profiler wrote no trace under {trace_dir}")
        summary = tr.reduce(tr.load(path))
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, name, section):
        value = reader(m["name"]).read(record, summary)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limit = cfg["limits"]["max_rel_err"]
    correct = (verdict["failed"] == 0
               and 0 < verdict["compared"] == verdict["kept"]
               and variants == {cfg["variant"]}
               and limit is not None and verdict["max_rel_err"] <= limit)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": len(dev["used"]), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if summary is not None:
        from chipbench import trace as tr
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = tr.breakdown(summary)
    lat = record["lateness_s"]
    result["info"] = {
        "requests": len(requests), "rate_per_s": len(requests) / seconds,
        "window_closed_late_s": closed - end,
        "lateness_max_s": max(lat) if lat else None,
        "lateness_mean_s": float(np.mean(lat)) if lat else None,
        "compiles_in_window": counter.count,
        "launches": record["launches"],
        "jobs_launched_in_window": record["jobs_launched_in_window"],
        "dispatch_counts": {p: dict(s.dispatch_counts)
                            for p, s in snap.pipelines.items()},
        "compared": verdict["compared"],
    }
    result["checks"] = {"max_rel_err": {"value": verdict["max_rel_err"],
                                        "limit": limit}}
    return result
