"""A bucket flush on a mesh: wide, overlapped spanning launches.

On a mesh whose shards are all healthy, ``SolverMux`` sends a flush's G
whole spanning units (``lanes * mesh_size`` jobs each) as the launches
of ``launch_plan(G)``, then the remaining groups per shard, two launches
in flight as on one device.  A spanning launch's planes go from the host
straight to each shard's slab; a per-shard launch is placed, and its
cost held on its shard, when it is begun.  Lanes are independent, so the
answers are bit-equal to one device's.  Here on the session's virtual
CPU devices, with the NR bucket's split re/im MMSE in interpret mode at
a small size.
"""
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.serve import FaultInjector, ManualClock, SolverMux
from repro.serve.core import EngineCore
from repro.serve.mux import launch_plan

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chipbench.jobs import nr_mmse  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="mesh tests need the 8-virtual-device session (conftest)")

MESH = 4
LANES = 2
UNIT = LANES * MESH
# nr100's shapes cut down, and its limit on max_rel_err
NR = {"prbs": 5 * UNIT + LANES + 1, "antennas": 8, "layers": 4, "rhs": 6,
      "sigma2": 0.1}
LIMIT = 8e-6
MARK = 7777.0               # a poisoned job's first yr entry


@pytest.fixture(scope="module")
def work():
    """Five spanning units, one more full group and a lone job."""
    return nr_mmse.make_request(NR, np.random.default_rng([2 ** 31 + 5, 0]))


def _serve(work, mesh, **kw):
    mux = SolverMux(lanes=LANES, clock=ManualClock(), mesh_size=mesh,
                    options={"mmse_equalize": {"sigma2": NR["sigma2"]}},
                    **kw)
    jobs = [mux.submit("mmse_equalize", *a) for a in work]
    mux.run()
    return mux, jobs


def _spy(monkeypatch):
    """Record each launch's start (its width, mesh sharding and inputs)
    and each gather, in the order the mux makes them."""
    order = []
    orig_call, orig_gather = EngineCore._timed_call, EngineCore._gather

    def call(self, fn, padded, device=None, fault_ctx=None):
        def entry(*inputs):
            order.append(("start", len(padded[0]), device, inputs, padded))
            return fn(*inputs)
        return orig_call(self, entry, padded, device, fault_ctx)

    def gather(self, answer, dt):
        order.append(("gather", len(answer)))
        return orig_gather(self, answer, dt)

    monkeypatch.setattr(EngineCore, "_timed_call", call)
    monkeypatch.setattr(EngineCore, "_gather", gather)
    return order


def test_answers_equal_one_device_and_meet_the_limit(work):
    mesh_mux, got = _serve(work, MESH)
    _, want = _serve(work, 1)
    assert [j.state for j in got] == ["done"] * len(work)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.out, w.out)
    ref = nr_mmse.reference(NR, work)
    out = np.stack([j.out for j in got]).astype(np.float64)
    err = np.max(np.abs(out - ref), axis=(1, 2)) \
        / np.max(np.abs(ref), axis=(1, 2))
    assert np.max(err) <= LIMIT
    assert {lr.variant for lr in mesh_mux.metrics().launches} \
        == {"split_complex"}


def test_spanning_units_go_as_the_launch_plan_two_in_flight(work,
                                                            monkeypatch):
    order = _spy(monkeypatch)
    mux, jobs = _serve(work, MESH)
    assert [j.state for j in jobs] == ["done"] * len(work)
    plan = launch_plan(5)
    assert plan == [2, 2, 1]
    widths = [p * UNIT for p in plan] + [LANES, LANES]
    assert [(e[0], e[1]) for e in order] == [
        ("start", widths[0]), ("start", widths[1]), ("gather", widths[0]),
        ("start", widths[2]), ("gather", widths[1]),
        ("start", widths[3]), ("gather", widths[2]),
        ("start", widths[4]), ("gather", widths[3]),
        ("gather", widths[4])]
    recs = mux.metrics().launches
    assert [(lr.mesh, lr.groups, lr.shard, lr.real) for lr in recs] == [
        (MESH, 2, -1, 16), (MESH, 2, -1, 16), (MESH, 1, -1, 8),
        (1, 1, recs[3].shard, 2), (1, 1, recs[4].shard, 1)]
    # the flush events carry the jobs in order, the wide ones their groups
    flushes = [e for e in mux.events if e["event"] == "flush"]
    assert [s for e in flushes for s in e["jobs"]] \
        == [j.seq for j in jobs]
    assert [e.get("groups") for e in flushes] == [2, 2, None, None, None]
    snap = mux.metrics()
    # per shard: 5 units of one group each, and one of the two groups
    assert sorted(st.lanes_dispatched for st in snap.shards.values()) \
        == [5 * LANES] * 2 + [6 * LANES] * 2


def test_spanning_inputs_go_to_each_shard_from_the_host(work, monkeypatch):
    order = _spy(monkeypatch)
    mux, _ = _serve(work, MESH)
    spanning = [e for e in order if e[0] == "start" and e[1] >= UNIT]
    assert len(spanning) == 3
    mesh_devices = list(mux.shards.devices)
    for _, width, device, inputs, padded in spanning:
        assert device == mux.shards.sharding
        per = width // MESH
        for x, plane in zip(inputs, padded):
            assert isinstance(x.sharding, NamedSharding)
            assert x.sharding == mux.shards.sharding
            shards = sorted(x.addressable_shards,
                            key=lambda sh: sh.index[0].start)
            assert [sh.device for sh in shards] == mesh_devices
            for k, sh in enumerate(shards):
                np.testing.assert_array_equal(
                    np.asarray(sh.data), plane[k * per:(k + 1) * per])
    # a per-shard launch's inputs are committed to its shard's device
    local = [e for e in order if e[0] == "start" and e[1] < UNIT]
    for _, _, device, inputs, _ in local:
        assert device in mesh_devices
        assert all(x.devices() == {device} for x in inputs)


def test_a_launch_begun_with_another_in_flight_is_placed_apart(
        work, monkeypatch):
    """After the spanning launches every shard carries the same settled
    load, so the full group goes to shard 0 (the lowest index).  The
    partial is begun while that group is in flight: its held cost sends
    the partial to another shard."""
    order = _spy(monkeypatch)
    mux, _ = _serve(work, MESH)
    local = [lr for lr in mux.metrics().launches if lr.mesh == 1]
    assert [lr.shard for lr in local] == [0, 1]
    starts = [e[2] for e in order if e[0] == "start" and e[1] < UNIT]
    assert starts == [mux.shards.devices[0], mux.shards.devices[1]]
    # nothing is held once the flush is done, and the settled load
    # counts each launch once, the spanning ones on every shard
    assert mux.shards._held == {}
    load = mux.shards.load
    assert load[0] == pytest.approx(load[1])
    assert load[2] == pytest.approx(load[3])
    assert load[2] < load[0]


def test_the_injector_keeps_one_unit_a_launch_in_order(work, monkeypatch):
    order = _spy(monkeypatch)
    mux, jobs = _serve(work, MESH, injector=FaultInjector({}, seed=0))
    assert [j.state for j in jobs] == ["done"] * len(work)
    kinds = [(e[0], e[1]) for e in order]
    assert kinds == [("start", UNIT), ("gather", UNIT)] * 5 \
        + [("start", LANES), ("gather", LANES)] * 2
    assert {lr.groups for lr in mux.metrics().launches} == {1}
    assert mux.metrics().total_merged_launches == 0


def test_a_failed_spanning_launch_decomposes_per_shard(work, monkeypatch):
    """A poisoned lane in a wide spanning launch: the launch decomposes
    into its lane groups, each launched on one shard; only the poisoned
    job fails, and the other answers equal the clean run's."""
    poisoned = [tuple(np.array(x) for x in a) for a in work]
    poisoned[3][2][0, 0] = MARK
    orig = EngineCore._timed_call

    def nan_lanes(self, fn, padded, device=None, fault_ctx=None):
        answer, dt = orig(self, fn, padded, device, fault_ctx)
        lanes = np.flatnonzero(padded[2][:, 0, 0] == MARK)
        if lanes.size:
            answer = np.array(answer)
            answer[lanes] = np.nan
        return answer, dt

    monkeypatch.setattr(EngineCore, "_timed_call", nan_lanes)
    mux, jobs = _serve(poisoned, MESH)
    _, clean = _serve(work, MESH)
    assert [j.seq for j in jobs if j.state == "failed"] == [jobs[3].seq]
    assert jobs[3].reason == "nonfinite_output"
    for j, c in zip(jobs, clean):
        if j.state == "done":
            np.testing.assert_array_equal(j.out, c.out)
    decomposed = [e for e in mux.events if e.get("action") == "decompose"]
    assert [e["jobs"] for e in decomposed][0] \
        == [j.seq for j in jobs[:2 * UNIT]]
    recs = mux.metrics().launches
    # the first launch's 16 jobs went again as 8 one-group launches,
    # each on one shard, and none wider
    assert sum(lr.mesh == 1 and lr.real + lr.padded == LANES
               for lr in recs) >= 2 * UNIT // LANES
    assert mux.shards._held == {}
