"""The reader of ``jobs_per_launch`` (``chipbench/metrics/jobs_per_launch.py``):
the jobs the window's launches served over the window's device events of
the served TPU custom call, on the slices of chip traces checked in under
``data/`` and on a written trace whose kernel is taken out."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SLICE = os.path.join(HERE, "data", "v5e_window_slice.xplane.pb")
SPANS_SLICE = os.path.join(HERE, "data", "v5e_spans_slice.xplane.pb")
SMALL = os.path.join(HERE, "data", "small_trace.pbtxt")
METRICS = ["jobs_per_launch.rt", "jobs_per_launch.bulk"]


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", [WINDOW_SLICE, SPANS_SLICE],
                         ids=["window_slice", "spans_slice"])
def test_two_kernel_events_by_hand(metric, path):
    """Each slice holds two launches of the split MMSE kernel (one op
    ``_unknown_.1``, the other ``mmse_split.1``): 16 jobs over two
    events."""
    summary = tr.reduce(tr.load(path))
    read = harness.reader(metric).read
    assert read({"jobs_launched_in_window": 16}, summary) \
        == pytest.approx(8.0)
    assert read({"jobs_launched_in_window": 13}, summary) \
        == pytest.approx(6.5)


def test_only_the_custom_call_counts():
    """The copies around the kernel are device events too; only the
    kernel's count."""
    summary = tr.reduce(tr.load(WINDOW_SLICE))
    assert sum(op["count"] for op in summary["ops"].values()) == 14
    assert harness.reader("jobs_per_launch").read(
        {"jobs_launched_in_window": 546}, summary) == pytest.approx(273.0)


def test_nothing_to_read_without_the_kernel():
    from jax.profiler import ProfileData
    read = harness.reader("jobs_per_launch").read
    record = {"jobs_launched_in_window": 16}
    # not traced
    assert read(record, None) is None
    with open(SMALL) as f:
        text = f.read()
    summary = tr.reduce(ProfileData.from_text_proto(text))
    assert read(record, summary) == pytest.approx(8.0)
    # the same trace with its one custom call made an ordinary op
    summary = tr.reduce(ProfileData.from_text_proto(
        text.replace('custom_call_target=\\"tpu_custom_call\\"', "")))
    assert not any(n.endswith("tpu_custom_call") for n in summary["ops"])
    assert read(record, summary) is None
