"""The four-carrier cell's parts on the CPU: its configuration against
``nr100_mimo64x16``, its entries, a whole run of the harness at a small
size on a mesh of four virtual devices, and the reader of
``shard_skew_pct`` on a small written trace of two devices."""
import os
import sys
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench import spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

from test_chipbench_spans import _write  # noqa: E402

CELL = "nr100x4.slot_bulk"
ONE = "nr100.slot_bulk"
OWN = {"name", "source", "deployment", "reduced", "carriers", "prbs", "mux",
       "assumed"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


@pytest.fixture(scope="module")
def cfg(bench):
    return harness.cell(bench, CELL)[1]


@pytest.fixture(scope="module")
def one(bench):
    return harness.cell(bench, ONE)[1]


def test_configuration_is_four_nr100_carriers(bench, cfg, one):
    entry, _, traffic = harness.cell(bench, CELL)
    assert set(cfg) == set(one) | {"carriers"}
    for key in set(one) - OWN:
        assert cfg[key] == one[key], key
    assert cfg["carriers"] == entry["chips"] == 4
    assert cfg["prbs"] == cfg["carriers"] * one["prbs"] == 4 * 273
    assert cfg["mux"] == dict(one["mux"], mesh_size=entry["chips"])
    assert cfg["limits"]["max_rel_err"] == one["limits"]["max_rel_err"]
    for key, why in one["assumed"].items():
        assert cfg["assumed"][key] == why, key
    assert set(cfg["assumed"]) == set(one["assumed"]) | {"carriers"}
    conf = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert conf["source"] == cfg["source"] and "C-RAN" in cfg["source"]
    assert cfg["deployment"] != one["deployment"]
    assert traffic == harness.cell(bench, ONE)[2]
    assert entry["traffic"] == "slot_bulk"


def test_reduced_is_the_carrier_count(bench, cfg):
    conf = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert conf["reduced"] == cfg["reduced"] == ["carriers"]


def test_the_cells_metrics_are_its_own(bench):
    e2e = [m["name"] for m in harness.metrics_for(bench, CELL, "end_to_end")]
    layer = [m for m in harness.metrics_for(bench, CELL, "per_layer")]
    assert sorted(e2e) == ["setup_s", "solves_per_s"]
    assert [m["name"] for m in layer] == [
        "device_idle_pct.x4", "mmse_split_roofline.x4", "jobs_per_launch.x4",
        "overlap_pct.x4", "admit_ms.x4", "stack_ms.x4", "copy_in_ms.x4",
        "sync_ms.x4", "shard_skew_pct.x4"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "solves_per_s"
               for m in layer)
    # and no other cell reports them
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not any(m["name"].endswith(".x4") for m in
                           harness.metrics_for(bench, w["name"],
                                               "per_layer"))


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs the 8-virtual-device session (conftest)")
def test_tiny_run_on_a_mesh_of_four_is_correct(bench, cfg):
    """Four carriers of 9 PRB, 8 antennas, 4 layers and 6 right-hand
    sides: a slot is one spanning unit (8 lanes on each of 4 devices)
    and a 4-job partial."""
    tiny = dict(cfg, prbs=4 * 9, antennas=8, layers=4, rhs=6)
    res = harness.run_cell(bench, CELL, 2 ** 31 + 11, 0.4, False,
                           time.monotonic(), cfg=tiny,
                           traffic={"loop": "closed", "outstanding": 2,
                                    "pool": 2})
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["device"]["count"] == 4
    assert res["info"]["dispatch_counts"] == {
        "mmse_equalize": {"split_complex": res["info"]["launches"]}}
    assert set(res["metrics"]) == {"solves_per_s", "setup_s"}


def test_control_fails_the_limit_at_the_cells_size(cfg):
    """The reference one precision below the program's, on one slot of
    the cell's four carriers, reads over the limit it shares with
    nr100."""
    kind = harness.job_kind(cfg)
    slot = harness.build_pool(kind, cfg, 2 ** 31 + 99, 1)[0]
    assert len(slot) == 1092
    worst = float(np.max(harness.rel_errors(kind.control(cfg, slot),
                                            kind.reference(cfg, slot))))
    assert worst > cfg["limits"]["max_rel_err"]


# Window [1000, 11000) ns.  Device 0: ops [2000, 4000) and [3000, 5000),
# busy 3000.  Device 1: [0, 1500) and [10500, 12000), cut at the window's
# edges, busy 1000.  Device 2 has only a module line, a CPU plane is not a
# device.  Mean 2000, max 3000: 100 * (3000 / 2000 - 1) = 50.
TWO_DEVICES = """
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%mmse_split.1 = k" } }
}
planes {
  id: 3
  name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 10500000 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%mmse_split.1 = k" } }
}
planes {
  id: 4
  name: "/device:TPU:2"
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_main" } }
}
planes {
  id: 5
  name: "/device:CPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion" } }
}
"""


def test_shard_skew_reads_by_hand(tmp_path, monkeypatch):
    text = tmp_path / "two.pbtxt"
    text.write_text(TWO_DEVICES)
    trace_dir = _write(str(text), tmp_path / "two")
    reader = harness.reader("shard_skew_pct.x4")
    busy = reader.busy_by_device(tr.load(tr.find_xplane(str(trace_dir))))
    assert busy == [pytest.approx(3000e-9), pytest.approx(1000e-9)]
    monkeypatch.setattr(spans, "TRACE_DIR", str(trace_dir))
    summary = {"devices": 2}        # the reduction's summary
    assert reader.read({}, summary) == pytest.approx(50.0)
    # not traced, or one busy device: nothing to read
    assert reader.read({}, None) is None
    assert reader.read({}, {"devices": 1}) is None
    assert reader.skew([1e-6]) is None
    assert reader.skew([2e-6, 2e-6, 2e-6, 2e-6]) == pytest.approx(0.0)
