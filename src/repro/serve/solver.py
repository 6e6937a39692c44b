"""Solver jobs and their dispatch: :class:`SolveJob` +
:class:`VariantDispatcher`.

A :class:`SolveJob` is one solver problem as the serving mux
(:class:`repro.serve.mux.SolverMux`) queues it; a
:class:`VariantDispatcher` resolves each of its shape buckets to a
registry variant and that variant's compiled entry point.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.experimental.layout import Format, Layout


@dataclasses.dataclass(eq=False)
class SolveJob:
    """One solver problem.  (``eq=False``: jobs are identity objects —
    the generated field-wise ``__eq__`` would compare numpy array args,
    which raises instead of answering.)

    ``args`` are the per-problem arrays WITHOUT the batch dimension
    (e.g. cholesky_solve: ``(a (N,N), b (N,M))``); ``out`` is filled by
    the serving engine.  ``pipeline`` and ``deadline`` (absolute clock
    seconds; ``None`` = no deadline) are used by :class:`SolverMux`;
    ``submitted_at``/``finished_at`` are stamped by the engine clock and
    feed the SLO metrics; ``seq`` is the mux's global arrival order (the
    FIFO tiebreak among equal-deadline buckets).

    ``priority`` is the overload-policy traffic class: ``"hard"`` jobs
    must never be shed and may preempt; ``"best_effort"`` jobs may be
    dropped once their deadline has expired.  ``state`` is the lifecycle
    marker — ``"queued"`` until a dispatch serves it (``"done"``, ``out``
    filled), the overload policy sheds it (``"dropped"``, terminal,
    ``out`` stays ``None``), or launch supervision gives up on it
    (``"failed"``, terminal, ``out`` stays ``None``, ``reason`` set to
    the structured failure reason — e.g. ``"nonfinite_input"`` rejected
    at submit, ``"nonfinite_output"`` for a persistently poisoned lane,
    or the exhausted-retries launch error).  A job is never silently
    lost: every submitted job ends in exactly one of those states.
    """

    PRIORITIES = ("hard", "best_effort")

    args: tuple
    out: np.ndarray | None = None
    pipeline: str | None = None
    deadline: float | None = None
    submitted_at: float | None = None
    finished_at: float | None = None
    seq: int = 0
    priority: str = "best_effort"
    state: str = "queued"
    reason: str | None = None
    dag: object | None = None
    """The :class:`repro.serve.mux.DagJob` this job is a stage of
    (``None`` for ordinary standalone jobs)."""
    stage: str | None = None
    """Stage name within ``dag`` (``None`` for standalone jobs)."""
    crit: bool = False
    """True when criticality planning (``DagSpec.criticality``) put this
    stage on the DAG's critical path — the mux admits critical-stage
    buckets ahead of slack ones at equal deadline."""

    def shape_key(self) -> tuple:
        """Shape bucket: per-arg (shape, dtype) — jobs sharing it can be
        stacked into one lane group / one compiled program."""
        return tuple((np.shape(a), str(np.asarray(a).dtype))
                     for a in self.args)


def resolve_pipeline_spec(pipeline: str):
    """Registry lookup + kind check for a servable solver pipeline."""
    from repro import kernels as K
    spec = K.get(pipeline)
    if spec.kind != "pipeline":
        raise ValueError(f"{pipeline!r} is a {spec.kind}, "
                         "not a servable pipeline")
    return spec


class VariantDispatcher:
    """Shape-bucket -> (Variant, jit'd fn) resolution with a per-variant
    compile cache, one per SolverMux lane pool.

    Every serve-side launch goes through :meth:`resolve` — the mux
    never touches ``spec.pallas`` directly — so a bucket of large or
    split-complex jobs transparently lands on the registry's fast
    variant, with one compiled program per variant x shape bucket.
    ``options`` (e.g. ``sigma2``) are bound into every variant entry
    point alike.

    ``cost_model`` (a :class:`repro.serve.cost.CostModel`, lazily
    defaulted) makes the dispatcher the one place a bucket flush gets
    priced: :meth:`price` resolves the bucket's variant and returns the
    estimated launch cost, so admission / preemption / coalescing
    decisions all price through the same dispatch the launch will use.

    ``shards`` (a :class:`repro.serve.shard.LaneShards`, optional) adds
    the mesh-spanning resolution path: :meth:`resolve_sharded` wraps the
    same options-bound variant entry point in ``shard_map`` over the
    lane mesh, cached per (variant, arity) alongside the single-device
    cache.

    **Demotion ladder.**  Launch supervision feeds per-bucket failure
    streaks back through :meth:`note_failure` / :meth:`note_success`.
    A variant that fails ``demote_after`` consecutive supervised
    launches on one shape bucket is *banned* for that bucket: resolution
    falls to the next applicable variant in registration order
    (tiled -> blocked -> base), so a buggy fast path degrades gracefully
    instead of failing the same jobs forever.  Only variants sharing the
    spec's calling convention (``variant.filler is None``) are
    demotable — a variant with its own filler (e.g. split-complex MMSE's
    4 planes) takes different arguments, so there is nothing below it to
    fall to and its jobs fail terminally instead.
    """

    def __init__(self, spec, options: dict | None = None, cost_model=None,
                 shards=None):
        self.spec = spec
        self.options = dict(options or {})
        self.cost_model = cost_model
        self.shards = shards
        self._fns: dict[str, object] = {}
        self._sharded_fns: dict[tuple, object] = {}
        self._bans: dict[tuple, set[str]] = {}
        self._fail_streaks: dict[tuple, int] = {}
        self.demotions: list[dict] = []

    def _dispatch(self, key: tuple):
        """``dispatch_key`` with this dispatcher's per-bucket bans
        applied: first applicable non-banned variant in registration
        order, the spec's base otherwise (base is never banned)."""
        shapes = tuple(tuple(s) for s, _ in key)
        dtypes = tuple(np.dtype(dt) for _, dt in key)
        banned = self._bans.get(key, ())
        for v in self.spec.variants:
            if v.name in banned:
                continue
            if v.when(shapes, dtypes):
                return v
        return self.spec.base

    def demotable(self, key: tuple, variant) -> bool:
        """True when a failing ``variant`` on ``key`` has somewhere to
        fall: it is not the base and it shares the spec's calling
        convention (``filler is None`` — same args, so the queued jobs
        can re-resolve to the demoted variant unchanged)."""
        return variant is not self.spec.base and variant.filler is None

    def note_failure(self, key: tuple, variant,
                     demote_after: int) -> object | None:
        """Account one supervised-launch failure of ``variant`` on shape
        bucket ``key``.  When the consecutive streak reaches
        ``demote_after`` and the variant is demotable, ban it for this
        bucket and return the variant resolution falls to (the mux turns
        that into a ``demote`` event + alert); otherwise return None."""
        sk = (key, variant.name)
        self._fail_streaks[sk] = self._fail_streaks.get(sk, 0) + 1
        if (demote_after > 0 and self._fail_streaks[sk] >= demote_after
                and self.demotable(key, variant)):
            self._bans.setdefault(key, set()).add(variant.name)
            self._fail_streaks.pop(sk, None)
            fallback = self._dispatch(key)
            self.demotions.append({
                "pipeline": self.spec.name, "key": key,
                "from": variant.name, "to": fallback.name})
            return fallback
        return None

    def note_success(self, key: tuple, variant) -> None:
        self._fail_streaks.pop((key, variant.name), None)

    def resolve(self, key: tuple):
        """``key`` is a SolveJob.shape_key(): per-arg ((shape, dtype)).
        Returns the dispatched registry Variant and its jit'd, options-
        bound entry point.

        The entry point returns its answer in the layout the compiler
        picks (``Layout.AUTO``), the one the kernel writes: the default
        layout of a wide batch can put the batch dimension minor (the
        TPU's at 128 lanes or more), which would cost a relayout copy
        of every answer on the device."""
        variant = self._dispatch(key)
        fn = self._fns.get(variant.name)
        if fn is None:
            fn = jax.jit(functools.partial(variant.fn, **self.options),
                         out_shardings=Format(Layout.AUTO))
            self._fns[variant.name] = fn
        return variant, fn

    def resolve_sharded(self, key: tuple):
        """Mesh-spanning counterpart of :meth:`resolve`: the same
        dispatched variant, wrapped over the lane mesh so the batch dim
        splits across shards.  Requires ``shards``."""
        if self.shards is None:
            raise ValueError(
                f"{self.spec.name!r} dispatcher has no lane shards; "
                "sharded resolution needs a mesh")
        variant = self._dispatch(key)
        cache_key = (variant.name, len(key))
        fn = self._sharded_fns.get(cache_key)
        if fn is None:
            fn = jax.jit(self.shards.wrap(
                functools.partial(variant.fn, **self.options), len(key)))
            self._sharded_fns[cache_key] = fn
        return variant, fn

    def price(self, key: tuple, lanes: int = 1, mesh: int = 1) -> float:
        """Estimated launch cost (cost-model seconds) of flushing one
        ``lanes``-wide grid of this shape bucket through whichever
        variant :meth:`resolve` dispatches it to.  ``mesh > 1`` prices
        the mesh-spanning form of the same flush (lanes split across
        shards, per-mesh launch overhead)."""
        if self.cost_model is None:
            from repro.serve.cost import CostModel
            self.cost_model = CostModel()
        variant, _ = self.resolve(key)
        shapes = tuple(shape for shape, _ in key)
        return self.cost_model.launch_cost(self.spec.name, variant,
                                           shapes, lanes, mesh=mesh)
