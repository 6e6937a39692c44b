"""Two launches in flight in a ``SolverMux`` bucket flush.

A flush of several lane groups begins launch k+1 (stack, copy in,
dispatch, the start of its copy back) before it gathers and finishes
launch k.  The answers, the event stream and the launch records are those
of the same groups flushed one at a time; every launch still goes through
``EngineCore._timed_call``; each ``LaunchRecord.measured`` is its own
launch's steps; a failure found at k's gather runs k's ladder there.
Where the serial order is part of the contract (a fault injector, the
overload policy) launches stay one at a time.
"""
import jax
import numpy as np
import pytest

from repro.launch.serve_solvers import job_args
from repro.serve import (FaultInjector, ManualClock, OverloadPolicy,
                         SolverMux)
from repro.serve.core import EngineCore

from test_serve_trace import LAUNCH, Ticks, program_spans

# three full pairs and one lone job on 2 lanes: a flush of three full
# groups still sends one group a launch (launch_plan; the merged launches
# of four or more are pinned in test_serve_launch_plan.py)
JOBS = 7
MARK = 7777.0               # a poisoned job's first right-hand side entry


def _args(jobs=JOBS, poison=()):
    """Cholesky solves; a job in ``poison`` carries ``MARK``, which
    :func:`poisoner` turns into a non-finite answer on every attempt."""
    out = []
    for seed in range(jobs):
        a, b = job_args("cholesky_solve", 8, 2, seed)
        if seed in poison:
            b[0, 0] = MARK
        out.append((a, b))
    return out


@pytest.fixture
def poisoner(monkeypatch):
    """Every launch's answer comes back NaN in the lanes that carry
    ``MARK``, still on the device, so the launch stays in flight."""
    orig = EngineCore._timed_call

    def poisoned(self, fn, padded, device=None, fault_ctx=None):
        answer, dt = orig(self, fn, padded, device, fault_ctx)
        lanes = np.flatnonzero(padded[1][:, 0, 0] == MARK)
        if lanes.size:
            answer = answer.at[lanes].set(np.nan)
        return answer, dt

    monkeypatch.setattr(EngineCore, "_timed_call", poisoned)


def _serve(args, groups=None, **kw):
    """Serve ``args`` on two lanes: all in one drain, or (``groups``)
    one drain per group of that many jobs, so no flush has two
    launches."""
    mux = SolverMux(lanes=2, clock=ManualClock(), **kw)
    jobs = []
    step = groups or len(args)
    for i in range(0, len(args), step):
        jobs += [mux.submit("cholesky_solve", *a) for a in args[i:i + step]]
        mux.run()
    return mux, jobs


def _records(mux):
    return [(lr.pipeline, lr.real, lr.padded, lr.variant)
            for lr in mux.metrics().launches]


@pytest.mark.parametrize("poison", [(), (2,)], ids=["sound", "poisoned"])
def test_overlapped_flush_equals_one_group_at_a_time(poisoner, poison):
    args = _args(poison=poison)
    over, got = _serve(args)
    one, want = _serve(args, groups=2)
    assert [j.state for j in got] == [j.state for j in want]
    assert ("failed" in [j.state for j in got]) == bool(poison)
    for g, w in zip(got, want):
        if w.state == "done":
            np.testing.assert_array_equal(g.out, w.out)
    assert over.events == one.events
    assert _records(over) == _records(one)
    assert len(_records(over)) == 4


def test_execute_of_the_next_launch_precedes_the_gather(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        mux, _ = _serve(_args())
    events = program_spans(tmp_path)
    names = [n for _, _, n in events]
    begin, end = list(LAUNCH[:3]), list(LAUNCH[3:])
    # launch k+1 begins before launch k ends; the last ends cold
    want = ["serve.mux.admit"] * JOBS + begin
    for _ in range(3):
        want += begin + end
    assert names == want + end
    assert len(mux.metrics().launches) == 4
    for (_, end_ns, _), (start, _, _) in zip(events, events[1:]):
        assert end_ns <= start


def test_every_launch_goes_through_the_timed_call(monkeypatch):
    calls = []
    orig = EngineCore._timed_call

    def counting(self, fn, padded, device=None, fault_ctx=None):
        calls.append(len(padded[0]))
        return orig(self, fn, padded, device, fault_ctx)

    monkeypatch.setattr(EngineCore, "_timed_call", counting)
    mux, jobs = _serve(_args())
    assert [j.state for j in jobs] == ["done"] * JOBS
    assert calls == [2] * len(mux.metrics().launches) == [2] * 4


def test_measured_excludes_the_next_launchs_steps():
    """On a clock that ticks a second a reading, launch k's gather comes
    after launch k+1's two readings; its wall is still its own four."""
    mux, _ = _serve(_args(), wall=Ticks())
    assert [lr.measured for lr in mux.metrics().launches] == [2.0] * 4


def test_a_poisoned_lane_runs_its_ladder_with_the_next_in_flight(
        monkeypatch, poisoner):
    order = []
    orig_call, orig_gather = EngineCore._timed_call, EngineCore._gather

    def call(self, fn, padded, device=None, fault_ctx=None):
        order.append(("start", None))
        return orig_call(self, fn, padded, device, fault_ctx)

    def gather(self, answer, dt):
        res, dt = orig_gather(self, answer, dt)
        order.append(("gather", bool(np.all(np.isfinite(res)))))
        return res, dt

    monkeypatch.setattr(EngineCore, "_timed_call", call)
    monkeypatch.setattr(EngineCore, "_gather", gather)
    mux, jobs = _serve(_args(jobs=4, poison=(0,)))
    # launch 1 is in flight while launch 0's two retries run
    assert [k for k, _ in order] == ["start", "start", "gather",
                                     "start", "gather", "start", "gather",
                                     "gather"]
    assert [ok for k, ok in order if k == "gather"] \
        == [False, False, False, True]
    assert [j.state for j in jobs] == ["failed", "done", "done", "done"]
    assert jobs[0].reason == "nonfinite_output"
    assert [e["event"] for e in mux.events] \
        == ["retry", "retry", "fail", "flush", "flush"]


def test_launches_stay_one_at_a_time_where_the_order_is_pinned(tmp_path):
    """A fault injector's draws are replayed in launch order, and the
    overload policy prices each admitted launch in turn: both flush
    their four full pairs one launch after another."""
    with jax.profiler.trace(str(tmp_path)):
        injected, a = _serve(_args(jobs=8),
                             injector=FaultInjector({}, seed=0))
        policed = SolverMux(lanes=2, clock=ManualClock(),
                            policy=OverloadPolicy())
        b = [policed.submit("cholesky_solve", *x) for x in _args(jobs=8)]
        policed.poll()
    assert [j.state for j in a + b] == ["done"] * 16
    assert len(injected.metrics().launches) \
        == len(policed.metrics().launches) == 4
    names = [n for _, _, n in program_spans(tmp_path)]
    admit = ["serve.mux.admit"] * 8
    assert names == admit + list(LAUNCH) * 4 + admit + list(LAUNCH) * 4
