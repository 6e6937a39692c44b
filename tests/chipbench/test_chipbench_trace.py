"""The benchmark's trace reduction, on a small written trace whose numbers
are worked out by hand in the trace file's header, and on a slice of a
trace recorded on the chip, worked out by hand below."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.pbtxt")


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """The text trace written as the profiler writes it, an
    ``.xplane.pb`` under ``plugins/profile/<run>/``."""
    from jax.profiler import ProfileData
    with open(DATA) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(raw)
    return d


@pytest.fixture(scope="module")
def summary(xplane):
    return tr.reduce(tr.load(tr.find_xplane(str(xplane))))


def test_finds_the_xplane_file(xplane, tmp_path):
    assert tr.find_xplane(str(xplane)).endswith("host.xplane.pb")
    assert tr.find_xplane(str(tmp_path)) is None


def test_window_busy_and_idle_by_hand(summary):
    assert summary["window_s"] == pytest.approx(10_000e-9)
    # [2500, 5000) + [6000, 7000) + [9500, 10000): the overlapping copy
    # counts once, the op past the window's end is cut, the module line
    # is not an op
    assert summary["busy_s"] == pytest.approx(4_000e-9)
    assert summary["devices"] == 1
    assert tr.idle_pct(summary) == pytest.approx(60.0)


def test_gaps_are_named_by_the_host_span(summary):
    assert [(n, pytest.approx(s)) for n, s in summary["gaps"]] == [
        ("submit", 2_500e-9), ("poll", 1_000e-9), ("wait", 2_500e-9)]
    assert sum(s for _, s in summary["gaps"]) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


def test_ops_are_named_short_and_matched_by_full_text(summary):
    assert set(summary["ops"]) == {
        "_unknown_.1 custom-call tpu_custom_call", "copy.2 copy"}
    s, n = tr.kernel(summary, lambda name, stats: any(
        "tpu_custom_call" in x for x in stats))
    assert (n, s) == (2, pytest.approx(3_000e-9))
    s, n = tr.kernel(summary, lambda name, stats: name.startswith("copy"))
    assert (n, s) == (2, pytest.approx(1_500e-9))
    assert tr.kernel(summary, lambda name, stats: False) == (0.0, 0)


def test_breakdown_lists_ops_and_gaps_longest_first(summary):
    b = tr.breakdown(summary)
    assert [n for n, _ in b["device_ops"]] == [
        "_unknown_.1 custom-call tpu_custom_call", "copy.2 copy"]
    assert [n for n, _ in b["idle_gaps"]][-1] == "poll"
    assert all(len(v) <= 10 for v in b.values())


def test_short_name_of_hlo_text():
    assert tr.short_name(
        "%copy-start.1 = (f32[8,64,144]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
        "copy-start(f32[8,64,144]{2,1,0:T(8,128)} %yr.1)") \
        == "copy-start.1 copy-start"
    assert tr.short_name("window") == "window"


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.union([]) == []


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        text = f.read().replace('name: "window"', 'name: "elsewhere"')
    with pytest.raises(ValueError, match="window"):
        tr.reduce(ProfileData.from_text_proto(text))


# A trace recorded on one TPU v5 lite (jax 0.9.0) during an open-loop run of
# nr100.slot_rt, cut to an 18.2 ms slice of its measured window: the window
# span was shortened to the slice and only events overlapping it were kept,
# as recorded.  Times below are ns from the window's start.
#
# Host spans (python3 line): wait [.., 1379539), generate [1407479,
# 1412149), submit [1418829, 14247869), poll [14265219, ..).
# Device "XLA Ops", two launches (the "XLA Modules" and "Async XLA Ops"
# lines are not counted):
#   copy-start [15088572, 15088576)       copy-start [18181048, 18181051)
#   copy-start.1 [15088577, 15088580)     copy-start.1 [18181053, 18181055)
#   copy [15088580, 15089250)             copy [18181055, 18181724)
#   copy-done [15089250, 15089253)        copy-done [18181727, 18181730)
#   copy.1 [15089255, 15089490)           copy.1 [18181730, 18181963)
#   copy-done.1 [15089490, 15089790)      copy-done.1 [18181965, 18182258)
#   kernel [15089792, 15183719)           kernel [18182259, 18276185),
#                                         cut at the window's end 18200000
# Busy union: 4 + 676 + 535 + 93927 (first launch) + 3 + 671 + 236 + 293
# + 17741 (second) = 114086 ns of 18200000.  The first gap, [0, 15088572),
# overlaps submit for 12829040 ns, more than wait, generate or poll; the
# longest gap after it, [15183719, 18181048) = 2997329 ns, lies in poll.
RECORDED = os.path.join(os.path.dirname(DATA), "v5e_window_slice.xplane.pb")
KERNEL = "_unknown_.1 custom-call tpu_custom_call"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce(tr.load(RECORDED))


def test_recorded_trace_busy_and_idle_by_hand(recorded):
    assert recorded["window_s"] == pytest.approx(18_200_000e-9)
    assert recorded["devices"] == 1
    assert recorded["busy_s"] == pytest.approx(114_086e-9, abs=1e-12)
    assert tr.idle_pct(recorded) == pytest.approx(
        100.0 * (18_200_000 - 114_086) / 18_200_000)


def test_recorded_trace_gaps_are_named_by_hand(recorded):
    gaps = recorded["gaps"]
    assert gaps[0] == ("submit", pytest.approx(15_088_572e-9, abs=1e-12))
    longest_after = max(gaps[1:], key=lambda g: g[1])
    assert longest_after == ("poll", pytest.approx(2_997_329e-9, abs=1e-12))
    assert sum(s for _, s in gaps) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"])


def test_recorded_trace_kernel_by_its_hlo_text(recorded):
    from chipbench.work import mmse_split
    assert set(recorded["ops"]) == {
        KERNEL, "copy copy", "copy.1 copy", "copy-start copy-start",
        "copy-start.1 copy-start", "copy-done copy-done",
        "copy-done.1 copy-done"}
    s, n = tr.kernel(recorded, mmse_split.match)
    assert n == 2 and s == pytest.approx(111_668e-9, abs=1e-12)
    assert tr.breakdown(recorded)["device_ops"][0][0] == KERNEL
