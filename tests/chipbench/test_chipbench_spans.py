"""The program's spans in a traced run (``chipbench/spans.py``) and the
readers of their metrics, on a small written trace whose numbers are
worked out by hand in the trace file's header and below."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "spans_trace.pbtxt")
NO_SPANS = os.path.join(HERE, "data", "small_trace.pbtxt")

# seconds per span over the whole trace: the window's two launches and
# the drain's one after it
TOTALS = {"serve.mux.admit": 1_600e-9, "serve.mux.stack": 400e-9,
          "serve.core.copy_in": 500e-9, "serve.core.execute": 300e-9,
          "serve.core.copy_out": 4_500e-9, "serve.mux.finish": 700e-9}


def _write(text_path, out_dir):
    """The text trace as the profiler writes it, an ``.xplane.pb`` under
    ``plugins/profile/<run>/``."""
    from jax.profiler import ProfileData
    with open(text_path) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    run = out_dir / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(raw)
    return out_dir


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    return _write(DATA, tmp_path_factory.mktemp("spans"))


@pytest.fixture(scope="module")
def pd(trace_dir):
    return tr.load(tr.find_xplane(str(trace_dir)))


def test_span_totals_by_hand(pd):
    got = spans.span_totals(pd)
    assert set(got) == set(TOTALS)
    for name, s in TOTALS.items():
        assert got[name] == pytest.approx(s, abs=1e-15), name


def test_program_spans_leave_the_reduction_as_it_was(pd):
    """The benchmark's own reduction reads the same busy time and names
    the gaps by its spans alone, as in ``small_trace.pbtxt``."""
    summary = tr.reduce(pd)
    assert summary["busy_s"] == pytest.approx(4_000e-9)
    assert [(n, pytest.approx(s)) for n, s in summary["gaps"]] == [
        ("submit", 2_500e-9), ("poll", 1_000e-9), ("wait", 2_500e-9)]
    assert set(summary["ops"]) == {"mmse_split.1 custom-call tpu_custom_call",
                                   "copy.2 copy"}


def test_idle_by_span_by_hand(pd):
    """Idle [0, 2500): admit 1600, stack 200, copy_in 200, execute 100,
    submit's uncovered [0, 200) and [1800, 2000).  Idle [5000, 6000):
    copy_out 500, finish 100, stack 100, copy_in 200, execute 100.  Idle
    [7000, 9500): copy_out 100, finish 500, poll's uncovered [7600,
    8000), nothing over [8000, 8300), wait [8300, 9500)."""
    got = spans.attribute(pd)
    want = {"serve.mux.admit": 1600, "serve.mux.stack": 300,
            "serve.core.copy_in": 400, "serve.core.execute": 200,
            "serve.core.copy_out": 600, "serve.mux.finish": 600,
            "submit": 400, "poll": 400, "wait": 1200, "other": 300}
    assert set(got["idle_by_span"]) == set(want)
    for name, ns in want.items():
        assert got["idle_by_span"][name] == pytest.approx(
            ns * 1e-9, abs=1e-15), name
    assert got["idle_s"] == pytest.approx(6_000e-9)
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        got["idle_s"])
    assert got["idle_in_leaves_pct"] == pytest.approx(100 * 3_700 / 6_000)


def test_idle_gaps_are_named_by_benchmark_span_and_leaf(pd):
    assert [(n, pytest.approx(s)) for n, s in spans.attribute(pd)[
        "idle_gaps"]] == [
        ("submit/serve.mux.admit", 2_500e-9),
        ("wait/serve.mux.finish", 2_500e-9),
        ("poll/serve.core.copy_out", 1_000e-9)]


def test_idle_gaps_without_program_spans_keep_the_benchmark_names(
        tmp_path):
    pd = tr.load(tr.find_xplane(str(_write(NO_SPANS, tmp_path))))
    got = spans.attribute(pd)
    assert [n for n, _ in got["idle_gaps"]] == ["submit", "wait", "poll"]
    assert got["idle_in_leaves_pct"] == 0.0
    assert spans.span_totals(pd) == {}


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read().replace('name: "window"', 'name: "elsewhere"')
    with pytest.raises(ValueError, match="window"):
        spans.attribute(ProfileData.from_text_proto(text))


@pytest.mark.parametrize("stem,span", [
    ("admit_ms", "serve.mux.admit"), ("stack_ms", "serve.mux.stack"),
    ("finish_ms", "serve.mux.finish"), ("copy_in_ms", "serve.core.copy_in"),
    ("execute_ms", "serve.core.execute"), ("sync_ms", "serve.core.copy_out"),
])
def test_reader_gives_the_span_in_ms_per_request(stem, span, trace_dir,
                                                 tmp_path, monkeypatch):
    summary = {"busy_s": 4e-6}          # the reduction's summary: any
    record = {"requests": 4}
    monkeypatch.setattr(spans, "TRACE_DIR", str(trace_dir))
    for suffix in (".rt", ".bulk"):
        got = harness.reader(stem + suffix).read(record, summary)
        assert got == pytest.approx(TOTALS[span] / 4 * 1e3)
    # not traced, or no request: nothing to read
    assert harness.reader(stem).read(record, None) is None
    assert harness.reader(stem).read({"requests": 0}, summary) is None
    # a trace without the program's spans (the parent's): nothing either
    monkeypatch.setattr(spans, "TRACE_DIR",
                        str(_write(NO_SPANS, tmp_path)))
    assert harness.reader(stem).read(record, summary) is None


# A trace recorded on one TPU v5 lite (jax 0.9.0) during a traced run of
# nr100.slot_bulk with the program's spans on, cut to a 5.9 ms slice of
# its measured window around the last submit of a slot and the next two
# launches: the window span was shortened to the slice, and of the host
# only the benchmark's thread and of the device only "XLA Ops" were kept,
# with the events overlapping the slice, as recorded.  Times below are ns
# from the window's start.
#
# Host spans: submit [.., 48620), admit [2000, 47011), poll [61360, ..);
#   launch 1: stack [197971, 335680) copy_in [348451, 1793111)
#     execute [1795911, 1979871) copy_out [1984200, 3204351)
#     finish [3238751, 3351011)
#   launch 2: stack [3363320, 3507340) copy_in [3518871, 4509951)
#     execute [4511951, 4783271) copy_out [4786340, 5782211)
#     finish [5814080, 5905720)
# Device "XLA Ops", busy runs of launch 1: [1880981, 1880985),
#   [1880987, 1881667), [1881669, 1881671), [1881673, 1881906),
#   [1881908, 1882195), kernel [1882196, 1976363): 95373 ns;
#   launch 2: [4557566, 4557569), [4557571, 4557573), [4557574, 4558520),
#   [4558521, 4558524), [4558526, 4558972), kernel [4558973, 4652900):
#   95327 ns.  Busy 190700 of 5906720 ns; both kernels end inside
#   execute, so the device is idle through every copy_out.
RECORDED = os.path.join(HERE, "data", "v5e_spans_slice.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_span_totals_by_hand(recorded):
    want = {"serve.mux.admit": 45_011,
            "serve.mux.stack": 137_709 + 144_020,
            "serve.core.copy_in": 1_444_660 + 991_080,
            "serve.core.execute": 183_960 + 271_320,
            "serve.core.copy_out": 1_220_151 + 995_871,
            "serve.mux.finish": 112_260 + 91_640}
    got = spans.span_totals(recorded)
    assert set(got) == set(want)
    for name, ns in want.items():
        assert got[name] == pytest.approx(ns * 1e-9, abs=1e-12), name


def test_recorded_idle_by_span_by_hand(recorded):
    got = spans.attribute(recorded)
    idle = 5_906_720 - 190_700
    assert got["idle_s"] == pytest.approx(idle * 1e-9, abs=1e-12)
    by = got["idle_by_span"]
    # execute less the busy runs inside it; the other leaves never
    # overlap a device op
    assert by["serve.core.execute"] == pytest.approx(
        (183_960 - 95_373 + 271_320 - 95_327) * 1e-9, abs=1e-12)
    assert by["serve.core.copy_out"] == pytest.approx(
        (1_220_151 + 995_871) * 1e-9, abs=1e-12)
    # idle under no leaf: submit [0, 2000) and [47011, 48620), nothing
    # over [48620, 61360), the rest of poll between its leaves
    assert by["submit"] == pytest.approx(3_609e-9, abs=1e-12)
    assert by["other"] == pytest.approx(12_740e-9, abs=1e-12)
    assert by["poll"] == pytest.approx(252_689e-9, abs=1e-12)
    assert sum(by.values()) == pytest.approx(idle * 1e-9, abs=1e-12)
    assert got["idle_in_leaves_pct"] == pytest.approx(
        100.0 * (idle - 3_609 - 12_740 - 252_689) / idle)


def test_recorded_gaps_and_kernel_by_name(recorded):
    from chipbench.work import mmse_split
    gaps = spans.attribute(recorded)["idle_gaps"]
    assert [(n, pytest.approx(s, abs=1e-12)) for n, s in gaps[:3]] == [
        ("poll/serve.core.copy_out", (4_557_566 - 1_976_363) * 1e-9),
        ("poll/serve.core.copy_in", 1_880_981e-9),
        ("poll/serve.core.copy_out", (5_906_720 - 4_652_900) * 1e-9)]
    summary = tr.reduce(recorded)
    assert summary["busy_s"] == pytest.approx(190_700e-9, abs=1e-12)
    assert tr.breakdown(summary)["device_ops"][0][0] \
        == "mmse_split.1 custom-call tpu_custom_call"
    s, n = tr.kernel(summary, mmse_split.match)
    assert n == 2 and s == pytest.approx((94_167 + 93_927) * 1e-9,
                                         abs=1e-12)
