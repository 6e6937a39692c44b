"""Common engine core shared by decode and solver serving.

Both engines (``DecodeEngine`` for LM decode, ``SolverMux`` for the
solver pipelines) are the same machine at this altitude: submitted
work, a fixed pool of ``lanes`` the device executes in lockstep, and a
batch lifecycle of *take → pad to the pool → dispatch → scatter results
→ record metrics*.  :class:`EngineCore` owns the shared clock, lane-pool
accounting (a :class:`repro.serve.metrics.Recorder`) and the measured
launch seam (:meth:`EngineCore._timed_call` / :meth:`EngineCore._gather`);
each engine keeps its own queue (``DecodeEngine`` one FIFO,
``SolverMux`` per-pipeline shape buckets) and implements how a batch is
executed.

Padding is registry-driven: a lane group short of the pool size is
filled from the pipeline's declared ``KernelSpec.filler`` — a benign
per-lane problem (identity system, zero right-hand side) whose result
is discarded.  There is deliberately no shape-sniffing fallback here;
a spec that wants to be served padded must declare its filler.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.metrics import MetricsSnapshot, Recorder
from repro.serve.trace import span


class ManualClock:
    """Deterministic clock for tests and trace replays: ``clock()``
    returns the current virtual time; ``advance()`` moves it."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t

    def __call__(self) -> float:
        return self.t


class EngineCore:
    """Lane-pool accounting + batch lifecycle, engine-agnostic.

    ``lanes`` is the lockstep pool width (decode: slot count; solvers:
    grid lanes per launch).  ``clock`` is any zero-arg callable returning
    seconds — ``time.monotonic`` by default, :class:`ManualClock` in
    tests/replays.  Engines call :meth:`record_launch` /
    :meth:`record_job` as batches complete and expose :meth:`metrics`.

    ``wall`` is the *measurement* clock (``time.perf_counter`` by
    default) used by :meth:`_timed_call` to stamp real launch wall-clock
    onto every :class:`~repro.serve.metrics.LaunchRecord` — deliberately
    separate from the scheduling ``clock`` so virtual-clock replays still
    measure true execution cost.  Each measured launch is also fed to
    :meth:`observe_launch`, the hook engines override to close the
    cost-model calibration loop (the base hook is a no-op).

    Deliberately queue-free: ``DecodeEngine`` keeps a single FIFO, the
    mux its per-pipeline shape buckets.
    """

    def __init__(self, lanes: int, clock=None, wall=None):
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        self.lanes = int(lanes)
        self.clock = clock if clock is not None else time.monotonic
        self.wall = wall if wall is not None else time.perf_counter
        self.recorder = Recorder()
        # optional repro.serve.faults.FaultInjector: None (the default)
        # keeps every launch path bit-identical to the uninjected stack
        self.injector = None

    # ---------------- accounting ----------------

    def record_launch(self, pipeline: str, shape: tuple, real: int,
                      padded: int, variant: str = "base",
                      coalesced: int = 0, measured: float = None,
                      mesh: int = 1, shard: int = 0,
                      pad_flops: float = 0.0, groups: int = 1) -> None:
        self.recorder.record_launch(
            pipeline, shape, real, padded, self.clock(), variant,
            coalesced, math.nan if measured is None else measured,
            mesh, shard, pad_flops, groups)

    def record_job(self, pipeline: str, item) -> None:
        """Stamp ``finished_at`` and log the job's latency sample (keyed
        by the item's priority class when it declares one)."""
        item.finished_at = self.clock()
        self.recorder.record_job(pipeline, item.submitted_at,
                                 item.finished_at,
                                 getattr(item, "priority", "best_effort"))

    def metrics(self) -> MetricsSnapshot:
        return self.recorder.snapshot()

    def reset_metrics(self) -> None:
        self.recorder.reset()

    def _timed_call(self, fn, padded: list, device=None,
                    fault_ctx: dict | None = None) -> tuple[object, float]:
        """Start one padded lane-group launch and measure its wall clock
        on ``self.wall``.  The one seam every launch goes through:
        deterministic tests replace it with a synthetic wall model to
        drive the calibration loop without real-timer noise.

        Returns ``(answer, dt)``: the answer is still in flight, its copy
        back to the host started (``copy_to_host_async``) right after
        dispatch, and ``dt`` is the wall of the steps taken so far.
        :meth:`_gather` waits for the answer and adds that wait, so a
        caller may start the next launch in between and the copy back
        lands while the host prepares it (``SolverMux`` does, in a
        bucket flush).  ``answer`` may also be a host array already (a
        ``nan`` fault below, or a wrapper of this method); gathering it
        costs nothing.

        ``device`` (a device or a sharding) is where a mesh-sharded mux
        puts the inputs, straight from the host: one shard's device for
        a placed launch, the mesh's ``NamedSharding`` for a spanning one,
        so each shard's slab goes to its own device and none passes
        through device 0.  ``None`` keeps the legacy default-device path
        untouched.

        ``fault_ctx`` identifies the attempt to an attached
        :class:`repro.serve.faults.FaultInjector` (``self.injector``):
        a drawn ``raise`` fault aborts BEFORE the kernel executes
        (:class:`~repro.serve.faults.InjectedLaunchError` — failed
        attempts cost no kernel time), a ``nan`` fault gathers the
        answer at once and poisons the drawn output lanes, a ``stall``
        fault inflates the measured wall-clock (never the scheduling
        clock).  With no injector or no context the call is exactly
        the legacy path.

        Inside the measured wall, the ``serve.core.copy_in`` and
        ``serve.core.execute`` spans (:mod:`repro.serve.trace`) mark its
        two steps while a profiler records; :meth:`_gather` adds the
        third, ``serve.core.copy_out``."""
        fault = None
        if self.injector is not None and fault_ctx is not None:
            ctx = dict(fault_ctx)
            ctx["inputs"] = padded
            fault = self.injector.draw(ctx)
            if fault is not None and fault.kind == "raise":
                from repro.serve.faults import InjectedLaunchError
                raise InjectedLaunchError(fault.reason)
        t0 = self.wall()
        with span("serve.core.copy_in"):
            if device is None:
                inputs = [jnp.asarray(p) for p in padded]
            else:
                inputs = [jax.device_put(p, device) for p in padded]
        with span("serve.core.execute"):
            out = fn(*inputs)
            out.copy_to_host_async()
        dt = self.wall() - t0
        if fault is not None:
            if fault.kind == "nan":
                out, dt = self._gather(out, dt)
                out = np.array(out)            # writable copy
                for lane in fault.lanes:
                    if 0 <= lane < out.shape[0]:
                        out[lane] = np.nan
            elif fault.kind == "stall":
                dt += fault.stall
        return out, dt

    def _gather(self, answer, dt: float) -> tuple[np.ndarray, float]:
        """The answer of a launch :meth:`_timed_call` started, on the
        host, and the launch's measured wall: ``dt`` plus the wait here
        (the ``serve.core.copy_out`` span).  The wall is the launch's
        own cost, never the steps of a launch started in between."""
        t0 = self.wall()
        with span("serve.core.copy_out"):
            res = np.asarray(answer)
        return res, dt + self.wall() - t0

    def observe_launch(self, spec, variant, key: tuple, lanes: int,
                       measured: float, mesh: int = 1) -> None:
        """Per-launch feedback hook: called after every measured launch
        with the dispatched variant, the bucket key, the full padded
        lane width, and the measured wall-clock seconds (plus the shard
        count for mesh-spanning launches; the single-device path never
        passes ``mesh``, so legacy 5-arg overrides keep working).  The
        base engine does nothing; cost-model-carrying engines override
        it to feed :meth:`repro.serve.cost.CostModel.observe`."""


def pad_group(spec, stacked: list[np.ndarray], lanes: int, variant=None
              ) -> tuple[list[np.ndarray], int]:
    """Pad a stacked arg group's batch dim up to a multiple of ``lanes``
    using the spec's (or the dispatched variant's) declared benign filler.

    ``stacked`` holds one batched array per kernel argument.  Returns the
    padded arrays and the pad count.  Raises if padding is needed but no
    filler is declared — padding semantics are the kernel's to declare,
    not the engine's to guess (the old "square 3-D arg ⇒ add identity"
    heuristic is exactly what this replaces).  A variant with its own
    calling convention (e.g. split-complex MMSE's 4 planes) declares its
    own filler; variants that only change the execution schedule inherit
    the spec's.
    """
    b = stacked[0].shape[0]
    pad = (-b) % lanes
    if pad == 0:
        return stacked, 0
    filler = spec.filler
    if variant is not None and variant.filler is not None:
        filler = variant.filler
    if filler is None:
        raise ValueError(
            f"pipeline {spec.name!r} declares no padding filler; cannot "
            f"pad a {b}-job group to the {lanes}-lane pool")
    lane = filler(tuple(a.shape[1:] for a in stacked),
                  tuple(a.dtype for a in stacked))
    if len(lane) != len(stacked):
        raise ValueError(
            f"{spec.name!r} filler returned {len(lane)} arrays for "
            f"{len(stacked)} kernel args")
    out = []
    for arr, fill in zip(stacked, lane):
        fill = np.asarray(fill, dtype=arr.dtype)
        if fill.shape != arr.shape[1:]:
            raise ValueError(
                f"{spec.name!r} filler shape {fill.shape} != per-lane "
                f"shape {arr.shape[1:]}")
        reps = np.broadcast_to(fill, (pad,) + fill.shape)
        out.append(np.concatenate([arr, reps], axis=0))
    return out, pad
