"""A bucket flush's full lane groups as a few wide launches.

``SolverMux`` sends the G full groups of a bucket flush as the launches
of ``launch_plan(G)`` (several groups each once G >= 4), the partial
group last, padded to ``lanes`` as before.  Each lane's arithmetic, the
filler and the order of the jobs are those of one group a launch, so the
answers are bit-equal; only the number of launches changes.  A failed
merged launch decomposes into its groups, each of which runs the one-group
ladder.  The serial paths (fault injector, overload policy) keep one group
a launch; on a mesh the spanning units go as ``launch_plan`` and the groups
left over one a launch, each on its own shard.
"""
import jax
import numpy as np
import pytest

from repro.launch.serve_solvers import job_args
from repro.serve import (FaultInjector, ManualClock, OverloadPolicy,
                         SolverMux)
from repro.serve.core import EngineCore
from repro.serve.mux import MAX_LAUNCH_GROUPS, launch_plan

MARK = 7777.0               # a poisoned job's first right-hand side entry


@pytest.mark.parametrize("groups,plan", [
    (0, []),
    (1, [1]),
    (2, [1, 1]),
    (3, [1, 1, 1]),
    (4, [2, 2]),
    (5, [2, 2, 1]),
    (34, [16, 16, 2]),
    (35, [16, 16, 2, 1]),
    (68, [32, 32, 4]),
    (200, [32] * 6 + [8]),
])
def test_launch_plan_table(groups, plan):
    assert launch_plan(groups) == plan
    assert sum(plan) == groups
    assert all(p <= MAX_LAUNCH_GROUPS and p & (p - 1) == 0 for p in plan)
    # two groups or more always make two launches or more
    assert len(plan) >= min(groups, 2)


def test_launch_plan_compiles_at_most_six_sizes():
    sizes = {p for g in range(1, 300) for p in launch_plan(g)}
    assert sizes == {1, 2, 4, 8, 16, 32}


def _chol(jobs, poison=()):
    out = []
    for seed in range(jobs):
        a, b = job_args("cholesky_solve", 8, 2, seed)
        if seed in poison:
            b[0, 0] = MARK
        out.append(("cholesky_solve", (a, b)))
    return out


def _mmse_split(jobs):
    """4-plane MMSE jobs, which the mux serves from ``split_complex``."""
    rng = np.random.default_rng(5)
    m, n, k = 12, 8, 2
    return [("mmse_equalize",
             tuple(rng.standard_normal(s).astype(np.float32)
                   for s in ((m, n), (m, n), (m, k), (m, k))))
            for _ in range(jobs)]


def _serve(work, groups=None, lanes=2, **kw):
    """Serve ``work`` on ``lanes`` lanes: all in one drain, or
    (``groups``) one drain per ``groups`` jobs."""
    mux = SolverMux(lanes=lanes, clock=ManualClock(), **kw)
    jobs = []
    step = groups or len(work)
    for i in range(0, len(work), step):
        jobs += [mux.submit(p, *a) for p, a in work[i:i + step]]
        mux.run()
    return mux, jobs


def _records(mux):
    return [(lr.real, lr.padded, lr.groups, lr.variant)
            for lr in mux.metrics().launches]


@pytest.mark.parametrize("make,variant", [(_chol, "base"),
                                          (_mmse_split, "split_complex")],
                         ids=["cholesky", "mmse_split"])
def test_merged_flush_equals_one_group_at_a_time(make, variant):
    work = make(9)                  # four full pairs and one lone job
    merged, got = _serve(work)
    one, want = _serve(work, groups=2)
    assert [j.state for j in got] == ["done"] * 9
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.out, w.out)
    assert _records(merged) == [(4, 0, 2, variant), (4, 0, 2, variant),
                                (1, 1, 1, variant)]
    assert _records(one) == [(2, 0, 1, variant)] * 4 + [(1, 1, 1, variant)]
    # the same jobs in the same order, in fewer flush events
    flat = lambda mux: [s for e in mux.events for s in e["jobs"]]  # noqa
    assert flat(merged) == flat(one) == list(range(1, 10))


def test_jobs_in_order_and_the_deadline_partial_last():
    mux = SolverMux(lanes=2, clock=ManualClock())
    jobs = [mux.submit(p, *a, deadline=0.0) for p, a in _chol(9)]
    done = mux.poll()
    assert done == jobs
    assert [(e["jobs"], e.get("groups")) for e in mux.events] == [
        ([1, 2, 3, 4], 2), ([5, 6, 7, 8], 2), ([9], None)]
    assert _records(mux) == [(4, 0, 2, "base"), (4, 0, 2, "base"),
                             (1, 1, 1, "base")]
    snap = mux.metrics()
    assert snap.total_merged_launches == 2
    assert snap.mean_launch_groups == pytest.approx(5 / 3)


def test_a_flush_of_full_groups_only_leaves_the_partial_queued():
    mux = SolverMux(lanes=2, clock=ManualClock())
    jobs = [mux.submit(p, *a) for p, a in _chol(9)]
    assert mux.poll() == jobs[:8]
    assert [j.state for j in jobs] == ["done"] * 8 + ["queued"]
    assert _records(mux) == [(4, 0, 2, "base")] * 2


def test_two_merged_launches_are_in_flight_at_once(monkeypatch):
    order = []
    orig_call, orig_gather = EngineCore._timed_call, EngineCore._gather

    def call(self, fn, padded, device=None, fault_ctx=None):
        order.append(("start", len(padded[0])))
        return orig_call(self, fn, padded, device, fault_ctx)

    def gather(self, answer, dt):
        order.append(("gather", len(answer)))
        return orig_gather(self, answer, dt)

    monkeypatch.setattr(EngineCore, "_timed_call", call)
    monkeypatch.setattr(EngineCore, "_gather", gather)
    mux, jobs = _serve(_chol(8))
    assert [j.state for j in jobs] == ["done"] * 8
    assert order == [("start", 4), ("start", 4), ("gather", 4),
                     ("gather", 4)]


def test_a_poisoned_lane_decomposes_then_fails_only_its_job(monkeypatch):
    """Every launch's answer comes back NaN in the lanes that carry
    ``MARK``: the merged launch decomposes into its groups, the sound
    group is served, and the poisoned group's ladder fails only its
    job.  No launch is wider than the plan's."""
    widths = []
    orig = EngineCore._timed_call

    def poisoned(self, fn, padded, device=None, fault_ctx=None):
        widths.append(len(padded[0]))
        answer, dt = orig(self, fn, padded, device, fault_ctx)
        lanes = np.flatnonzero(padded[1][:, 0, 0] == MARK)
        if lanes.size:
            answer = answer.at[lanes].set(np.nan)
        return answer, dt

    monkeypatch.setattr(EngineCore, "_timed_call", poisoned)
    mux, jobs = _serve(_chol(8, poison=(2,)))
    _, clean = _serve(_chol(8))
    assert [j.state for j in jobs] == ["done"] * 2 + ["failed"] \
        + ["done"] * 5
    assert jobs[2].reason == "nonfinite_output"
    for j, c in zip(jobs, clean):
        if j.state == "done":
            np.testing.assert_array_equal(j.out, c.out)
    assert set(widths) == {2, 4}
    kinds = [(e["event"], e.get("action"), e.get("jobs", e.get("seq")))
             for e in mux.events]
    assert kinds[0] == ("retry", "decompose", [1, 2, 3, 4])
    assert ("fail", None, 3) in kinds
    assert [e["jobs"] for e in mux.events if e["event"] == "flush"] \
        == [[1, 2], [3, 4], [5, 6, 7, 8]]
    assert [lr.groups for lr in mux.metrics().launches] == [1, 1, 2]


def test_injector_and_policy_keep_one_group_a_launch():
    injected, a = _serve(_chol(8), injector=FaultInjector({}, seed=0))
    policed = SolverMux(lanes=2, clock=ManualClock(),
                        policy=OverloadPolicy())
    b = [policed.submit(p, *x) for p, x in _chol(8)]
    policed.poll()
    assert [j.state for j in a + b] == ["done"] * 16
    for mux in (injected, policed):
        assert _records(mux) == [(2, 0, 1, "base")] * 4
        assert mux.metrics().total_merged_launches == 0
        assert all("groups" not in e for e in mux.events)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="mesh tests need the 8-virtual-device session")
def test_mesh_keeps_one_group_a_launch():
    """Per shard, a mesh still launches one group at a time: 18 jobs on
    2 lanes and 2 shards are 4 spanning units, sent as ``launch_plan(4)``
    (two launches of 2 units, 2 groups a shard), then the group left over
    on one shard."""
    mux, jobs = _serve(_chol(18), mesh_size=2)
    assert [j.state for j in jobs] == ["done"] * 18
    assert [(lr.mesh, lr.groups, lr.real + lr.padded)
            for lr in mux.metrics().launches] == [(2, 2, 8), (2, 2, 8),
                                                  (1, 1, 2)]
    # the spanning launches hold 4 lanes a shard, the per-shard one 2
    assert {lr.shard for lr in mux.metrics().launches} == {-1, 0}
