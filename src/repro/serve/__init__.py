"""Serving stack for the FGOP reproduction.

Layout (one concern per module; the two engines, SolverMux and
DecodeEngine, share the ``EngineCore`` clock, lane-pool accounting and
launch seam):

  core     EngineCore, ManualClock, registry-driven pad_group
  decode   DecodeEngine / Request       (LM continuous batching:
                                         per-slot positions, paged KV
                                         slot reuse, per-slot sampling;
                                         attaches to SolverMux)
  solver   SolveJob / VariantDispatcher (solver jobs; shape bucket ->
                                         variant + compiled entry point)
  mux      SolverMux / OverloadPolicy   (mixed pipelines, shape-bucketed
                                         continuous batching, deadline-
                                         aware flush; admission control,
                                         preemption, coalescing)
  cost     CostModel / DriftStat        (self-tuning launch pricing:
                                         offline calibration from
                                         BENCH_pipelines.json + online
                                         re-fit from measured launches,
                                         drift observability)
  config   ServeConfig / global_config  (REPRO_SERVE_* env-tunable knobs
                                         for calibration + thresholds)
  tuning   BucketTuner                  (observed-traffic flush
                                         thresholds: max_wait, pressure)
  metrics  SLO dataclasses: p50/p99 latency (overall + per priority),
           throughput, lane utilization, padded-lane waste, dropped/
           preempted/coalesced counters, per-shard utilization
  shard    LaneShards                   (mesh-sharded lane pools:
                                         shard_map wrapping, placement,
                                         per-shard load accounting +
                                         quarantine/probe health)
  faults   FaultInjector                (seeded fault injection driving
                                         the launch-supervision /
                                         quarantine / demotion paths)
  trace    span                         (named host spans of the launch
                                         path on the profiler's clock,
                                         on while a profiler records)

The kernel registry (``repro.kernels``) is the routing table: any
``kind="pipeline"`` spec is servable, and its declared ``filler``
supplies benign padding lanes.
"""
from repro.serve.config import ServeConfig, global_config  # noqa: F401
from repro.serve.core import EngineCore, ManualClock, pad_group  # noqa: F401
from repro.serve.cost import (CostModel, DriftStat,  # noqa: F401
                              RobustEstimator)
from repro.serve.faults import (Fault, FaultInjector,  # noqa: F401
                                InjectedLaunchError)
from repro.serve.metrics import (DagStats, DecodeStats,  # noqa: F401
                                 DropRecord, FailRecord, FaultStats,
                                 LatencyStats, LaunchRecord,
                                 MetricsSnapshot, PipelineStats, Recorder,
                                 ShardStats, shard_stats)
from repro.serve.mux import DagJob, OverloadPolicy, SolverMux  # noqa: F401
from repro.serve.shard import LaneShards  # noqa: F401
from repro.serve.solver import SolveJob, VariantDispatcher  # noqa: F401
from repro.serve.tuning import BucketTuner  # noqa: F401


def __getattr__(name):
    # decode pulls in the whole repro.models transformer stack; load it
    # lazily (PEP 562) so solver-only consumers don't pay for it
    if name in ("DecodeEngine", "Request"):
        from repro.serve import decode
        return getattr(decode, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "EngineCore", "ManualClock", "pad_group",
    "DecodeEngine", "Request",
    "SolveJob", "SolverMux", "VariantDispatcher",
    "DagJob", "DagStats", "DecodeStats",
    "OverloadPolicy", "CostModel", "DriftStat", "RobustEstimator",
    "ServeConfig", "global_config", "BucketTuner",
    "DropRecord", "FailRecord", "FaultStats", "LatencyStats",
    "LaunchRecord", "MetricsSnapshot",
    "PipelineStats", "Recorder", "ShardStats", "shard_stats",
    "LaneShards", "Fault", "FaultInjector", "InjectedLaunchError",
]
