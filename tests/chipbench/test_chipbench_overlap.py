"""The reader of ``overlap_pct`` (``chipbench/metrics/overlap_pct.py``):
the share of launches dispatched while an earlier launch's answer was
still ungathered, on a small written trace in the pipelined order and on
the recorded trace of the serial order."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

from test_chipbench_spans import NO_SPANS, RECORDED, _write  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
PIPELINED = os.path.join(HERE, "data", "overlap_trace.pbtxt")


@pytest.fixture(scope="module")
def pipelined_dir(tmp_path_factory):
    return _write(PIPELINED, tmp_path_factory.mktemp("overlap"))


def test_the_recorded_serial_slice_reads_zero():
    """Each of the slice's two launches is gathered before the next is
    dispatched, as the mux launched them before it overlapped them."""
    share = harness.reader("overlap_pct").share
    assert share(tr.load(RECORDED)) == 0.0


def test_the_pipelined_trace_reads_by_hand(pipelined_dir):
    """Launches 1, 2 and 3 are dispatched with the one before them not
    yet gathered; launch 0 and the drain's lone launch 4 are not."""
    pd = tr.load(tr.find_xplane(str(pipelined_dir)))
    assert harness.reader("overlap_pct").share(pd) == pytest.approx(60.0)


@pytest.mark.parametrize("metric", ["overlap_pct.rt", "overlap_pct.bulk"])
def test_reader_reads_the_traced_run(metric, pipelined_dir, tmp_path,
                                     monkeypatch):
    record = {"requests": 4}
    summary = {"busy_s": 1e-6}      # the reduction's summary: any
    monkeypatch.setattr(spans, "TRACE_DIR", str(pipelined_dir))
    assert harness.reader(metric).read(record, summary) \
        == pytest.approx(60.0)
    # not traced: nothing to read
    assert harness.reader(metric).read(record, None) is None
    # a trace without the program's spans: nothing either
    monkeypatch.setattr(spans, "TRACE_DIR",
                        str(_write(NO_SPANS, tmp_path)))
    assert harness.reader(metric).read(record, summary) is None
