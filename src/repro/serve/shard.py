"""Mesh-sharded lane pools: :class:`LaneShards`.

The paper's lane dimension is batch-parallel — every pipeline grid
declares it ``("parallel", ...)`` — so a flush's lane axis shards
trivially across a 1-D device mesh: each device executes its own slab
of lanes in lockstep and the outputs gather back.  ``LaneShards`` is
the serve-side handle on that mesh:

  * **wrapping** — :meth:`wrap` turns a pipeline entry point into its
    mesh-spanning form via
    :func:`repro.distributed.sharding.shard_map` (``P(axis)`` on
    the batch dim of every input and output; trailing dims replicated).
    Because lanes are independent, the sharded program is bit-identical
    to the single-device launch on the same batch — the property the
    sharded-serve tests pin.
  * **placement** — a spanning launch's inputs go from the host straight
    to each shard's slab (:attr:`sharding`); non-spanning launches are
    committed to one shard's device (:attr:`devices`); :meth:`pick`
    chooses the least-loaded shard (optionally budget-first, for the
    mux's per-shard admission).
  * **load accounting** — a launch's priced cost is *held* on its shard
    (every shard, when spanning) from the moment it is placed
    (:meth:`hold`) and *settled* into :attr:`load` when it finishes
    (:meth:`settle`), or released if it is abandoned (:meth:`release`),
    so a launch placed while others are in flight sees theirs.
    :meth:`note` / :meth:`note_all` add settled cost directly;
    :meth:`imbalance` is the max/mean skew of the settled load the
    metrics snapshot reports.
  * **health** — per-shard consecutive-failure streaks
    (:meth:`note_failure` / :meth:`note_success`).  A shard whose
    streak reaches the quarantine threshold is **quarantined**: it
    stops receiving placements (:meth:`pick` restricted to
    :meth:`healthy`), aggregate capacity shrinks, and the mux stops
    offering mesh-spanning launches (which would execute on the dead
    device).  After ``probe_after`` scheduling-clock seconds the shard
    becomes :meth:`probe_due`: the mux routes one real launch at it as
    a probe — success reinstates (:meth:`reinstate`), failure re-arms
    the quarantine timer.

A ``LaneShards`` over a 1-device mesh is legal but pointless — the mux
only constructs one for ``mesh_size > 1`` so the single-device path
stays exactly the code it always was.
"""
from __future__ import annotations

import itertools
import math

import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import shard_map


class LaneShards:
    """One 1-D lane mesh + per-shard load accounting for a SolverMux."""

    def __init__(self, mesh, axis: str = "data"):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis: "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.devices = tuple(np.ravel(mesh.devices))
        self.size = len(self.devices)
        self.sharding = NamedSharding(mesh, P(axis))
        self.load = [0.0] * self.size
        # launches placed and not yet finished: token -> (shard or None
        # for all shards, priced cost)
        self._held: dict[int, tuple[int | None, float]] = {}
        self._tokens = itertools.count()
        # per-shard health: consecutive launch-failure streaks and
        # quarantine state (see the module docstring)
        self.fail_streak = [0] * self.size
        self.quarantined_at: list[float | None] = [None] * self.size
        self.quarantines = 0            # lifetime count (metrics)
        self.reinstatements = 0
        self.recovery_times: list[float] = []

    @classmethod
    def build(cls, size: int, axis: str = "data") -> "LaneShards":
        """Construct over the first ``size`` local devices (on CPU this
        needs virtual devices — :mod:`repro.launch.xla_env`)."""
        from repro.launch.mesh import make_lane_mesh
        return cls(make_lane_mesh(size, axis=axis), axis=axis)

    # ---------------- sharded launch path ----------------

    def wrap(self, fn, nargs: int):
        """Mesh-spanning form of a pipeline entry point: batch dim 0 of
        all ``nargs`` inputs and of the output is split over the lane
        axis; each shard sees its own contiguous lane slab.  The caller
        is responsible for padding the batch to a multiple of
        ``size * lanes_per_device`` so no shard sees a partial
        remainder (``SolverMux`` pads a mesh-spanning launch to the
        full ``lanes * mesh`` width)."""
        spec = P(self.axis)
        return shard_map(fn, mesh=self.mesh,
                         in_specs=(spec,) * nargs, out_specs=spec)

    # ---------------- health / quarantine ----------------

    def quarantined(self, shard: int) -> bool:
        return self.quarantined_at[shard] is not None

    def healthy(self) -> list[int]:
        """Shards eligible for placement (not quarantined)."""
        return [s for s in range(self.size) if not self.quarantined(s)]

    def all_healthy(self) -> bool:
        return all(q is None for q in self.quarantined_at)

    def note_failure(self, shard: int, t: float,
                     threshold: int) -> bool:
        """Account one launch failure on ``shard`` at scheduling time
        ``t``.  Returns True when this failure newly quarantines the
        shard (streak reached ``threshold``); a failure on an
        already-quarantined shard (a failed probe) re-arms its timer
        instead."""
        self.fail_streak[shard] += 1
        if self.quarantined(shard):
            self.quarantined_at[shard] = t          # re-arm probe timer
            return False
        if threshold > 0 and self.fail_streak[shard] >= threshold:
            self.quarantined_at[shard] = t
            self.quarantines += 1
            return True
        return False

    def note_success(self, shard: int) -> None:
        self.fail_streak[shard] = 0

    def probe_due(self, t: float, after: float) -> list[int]:
        """Quarantined shards whose sit-out window has elapsed — each is
        owed one probe launch."""
        return [s for s in range(self.size)
                if self.quarantined_at[s] is not None
                and t - self.quarantined_at[s] >= after]

    def reinstate(self, shard: int, t: float,
                  quarantined_since: float) -> float:
        """Return a probed shard to service; returns its downtime (the
        time-to-recover observable)."""
        downtime = t - quarantined_since
        self.quarantined_at[shard] = None
        self.fail_streak[shard] = 0
        self.reinstatements += 1
        self.recovery_times.append(downtime)
        return downtime

    # ---------------- placement / balancing ----------------

    def pick(self, budgets: list[float] | None = None,
             among: list[int] | None = None) -> int:
        """Shard for the next non-spanning launch: most remaining
        budget first (when per-shard budgets are in play), least
        accumulated load second, lowest index last — deterministic, so
        replayed traces place identically.  ``among`` restricts the
        candidates (the mux passes :meth:`healthy` while any shard is
        quarantined; an empty restriction falls back to all shards).
        The load it reads counts the launches still held in flight."""
        shards = among if among else range(self.size)
        if budgets is None:
            return max(shards, key=lambda s: (-self.seen(s), -s))
        return max(shards, key=lambda s: (budgets[s], -self.seen(s), -s))

    def seen(self, shard: int) -> float:
        """Settled load of ``shard`` plus the cost held on it by launches
        in flight (exactly the settled load when none is)."""
        return self.load[shard] + sum(
            cost for s, cost in self._held.values()
            if s is None or s == shard)

    def hold(self, shard: int | None, cost: float) -> int:
        """Hold ``cost`` on ``shard`` (``None``: every shard, a spanning
        launch) for a launch just placed; returns the token that
        :meth:`settle` or :meth:`release` takes."""
        token = next(self._tokens)
        self._held[token] = (shard, cost)
        return token

    def settle(self, token: int, cost: float) -> None:
        """The held launch finished: its ``cost``, priced now, joins the
        settled load of the shard it is held on."""
        shard, _ = self._held.pop(token)
        if shard is None:
            self.note_all(cost)
        else:
            self.note(shard, cost)

    def release(self, token: int | None) -> float:
        """Drop a hold without settling it (the launch is being
        re-placed, or handed its jobs to launches of their own) and
        return the cost it held; 0.0 for a token already settled or
        released."""
        return self._held.pop(token, (None, 0.0))[1]

    def note(self, shard: int, cost: float) -> None:
        self.load[shard] += cost

    def note_all(self, cost: float) -> None:
        """A mesh-spanning launch occupies every shard for its
        duration."""
        for s in range(self.size):
            self.load[s] += cost

    def imbalance(self) -> float:
        """max/mean accumulated load across shards (1.0 = perfectly
        balanced; NaN before any launch)."""
        total = sum(self.load)
        if total <= 0.0:
            return math.nan
        return max(self.load) / (total / self.size)
