"""The serving path's spans (``repro.serve.trace``).

Off unless a profiler records: no annotation is built.  While one
records, each launch of a ``SolverMux`` leaves its host steps as leaf
spans on the profiler's clock, read back here through the benchmark's
trace reader; the scheduling decisions and ``LaunchRecord.measured`` are
the same with the spans on as off.
"""
import json
import os
import pathlib
import sys

import jax
import pytest

from repro.launch.serve_solvers import (job_args, load_trace, replay_trace,
                                        run_chaos)
from repro.serve import (CostModel, FaultInjector, ManualClock,
                         OverloadPolicy, SolverMux)
from repro.serve import trace as st

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import spans as cs  # noqa: E402
from chipbench import trace as tr  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
LAUNCH = ("serve.mux.stack", "serve.core.copy_in", "serve.core.execute",
          "serve.core.copy_out", "serve.mux.finish")


class Ticks:
    """A measurement clock that advances one second per reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def serve(jobs: int = 5, injector=None):
    """``jobs`` Cholesky solves on two lanes: the full pairs launch in
    ``poll``, the odd one in the drain."""
    mux = SolverMux(lanes=2, clock=ManualClock(), wall=Ticks(),
                    injector=injector)
    served = [mux.submit("cholesky_solve", *job_args("cholesky_solve", 8, 2,
                                                     seed))
              for seed in range(jobs)]
    mux.poll()
    mux.run()
    assert [j.state for j in served] == ["done"] * jobs
    return mux


def program_spans(trace_dir) -> list:
    return cs.host_events(tr.load(tr.find_xplane(str(trace_dir))),
                          prefix=cs.PREFIX)


def test_off_builds_no_annotation(monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            made.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(st, "TraceAnnotation", Counting)
    assert st.span("a") is st.span("b")         # the one shared no-op
    serve()
    assert made == []


def test_on_each_launch_emits_its_leaves_in_order(tmp_path):
    """The poll's two full pairs overlap: the second launch is stacked,
    copied in and dispatched before the first is gathered and finished.
    The drain's lone launch runs its five steps back to back."""
    with jax.profiler.trace(str(tmp_path)):
        mux = serve()
    events = program_spans(tmp_path)
    names = [n for _, _, n in events]
    launches = len(mux.metrics().launches)
    assert launches == 3
    begin, end = list(LAUNCH[:3]), list(LAUNCH[3:])
    assert names == (["serve.mux.admit"] * 5 + begin + begin + end + end
                     + list(LAUNCH))
    # leaves: none overlaps the next
    for (_, end_ns, _), (start, _, _) in zip(events, events[1:]):
        assert end_ns <= start


def test_the_core_spans_lie_inside_the_measured_wall(tmp_path):
    """Each launch's copy_in, execute and copy_out follow one another
    (the next launch's copy_in and execute come between the last two),
    and together come to no more than the launch's wall on the real
    clock."""
    with jax.profiler.trace(str(tmp_path)):
        mux = SolverMux(lanes=2, clock=ManualClock())
        for seed in range(4):
            mux.submit("cholesky_solve",
                       *job_args("cholesky_solve", 8, 2, seed))
        mux.run()
    events = program_spans(tmp_path)
    core = [e for e in events if e[2].startswith("serve.core.")]
    walls = [lr.measured for lr in mux.metrics().launches]
    assert len(core) == 3 * len(walls) == 6
    assert [n for _, _, n in core] == list(LAUNCH[1:3]) * 2 \
        + [LAUNCH[3]] * 2
    for i, wall in enumerate(walls):
        steps = [core[2 * i], core[2 * i + 1], core[4 + i]]
        assert [n for _, _, n in steps] == list(LAUNCH[1:4])
        for (_, end_ns, _), (start, _, _) in zip(steps, steps[1:]):
            assert end_ns <= start
        assert sum(e - s for s, e, _ in steps) * 1e-9 <= wall


def test_measured_is_the_same_with_spans_on(tmp_path):
    off = [lr.measured for lr in serve().metrics().launches]
    with jax.profiler.trace(str(tmp_path)):
        on = [lr.measured for lr in serve().metrics().launches]
    # four readings of the ticking measurement clock per launch: around
    # its copy in and dispatch, and around its gather; the next launch's
    # readings in between are not its own
    assert on == off == [2.0, 2.0, 2.0]


def test_an_injected_stall_inflates_only_measured(tmp_path):
    def stalled():
        return FaultInjector({"stall_rate": 1.0, "stall_s": 30.0}, seed=3)

    off = serve(injector=stalled())
    with jax.profiler.trace(str(tmp_path)):
        on = serve(injector=stalled())
    for mux in (off, on):
        assert [lr.measured for lr in mux.metrics().launches] \
            == [32.0, 32.0, 32.0]
    assert on.events == off.events
    # the spans time the real steps; the stall is added to measured only
    core = [e - s for s, e, n in program_spans(tmp_path)
            if n.startswith("serve.core.")]
    assert len(core) == 9 and sum(core) * 1e-9 < 30.0


def test_overload_golden_replays_with_spans_on(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        mux = replay_trace(load_trace(DATA / "overload_trace.json"),
                           lanes=2, pressure=4, policy=OverloadPolicy(
                               budget=6.5e-5, cost_model=CostModel()))
    want = json.loads((DATA / "overload_golden.json").read_text())
    assert json.loads(json.dumps(mux.events)) == want
    assert {n for _, _, n in program_spans(tmp_path)} \
        == {"serve.mux.admit", *LAUNCH}


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="the chaos replay needs the 8-virtual-device "
                           "session (conftest)")
def test_chaos_golden_replays_with_spans_on(tmp_path):
    """Raised, poisoned and placed launches (retry, bisect, quarantine)
    with the spans on give the committed event stream."""
    with jax.profiler.trace(str(tmp_path)):
        faulted = run_chaos(DATA / "fault_trace.json")
    want = json.loads((DATA / "chaos_golden.json").read_text())
    assert json.loads(json.dumps(faulted["events"])) == want
    names = [n for _, _, n in program_spans(tmp_path)]
    assert names.count("serve.core.copy_in") \
        == names.count("serve.core.execute")
