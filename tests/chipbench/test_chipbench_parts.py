"""The benchmark's parts on the CPU: its file, the work and peak tables,
the job kinds' inputs, references and controls, and the variant each
cell's bucket dispatches to."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.jobs import nr_mmse  # noqa: E402
from chipbench.work import mmse_split  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CONFIGS = os.path.join(ROOT, "chipbench", "configs")

NR_TINY = {"job_kind": "nr_mmse", "prbs": 5, "antennas": 8, "layers": 4,
           "rhs": 6, "sigma2": 0.1}


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


# ---------------- BENCHMARK.json ----------------

def test_benchmark_file_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][1] == "chipbench/run.py"
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[s]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_every_cell_has_its_files_and_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in bench["workloads"]:
        entry, cfg, traffic = harness.cell(bench, w["name"])
        assert w["chips"] in (1, 4)
        assert traffic["loop"] in harness.LOOPS
        kind = harness.job_kind(cfg)
        assert callable(kind.reference) and callable(kind.control)
        mine = harness.metrics_for(bench, w["name"], "end_to_end")
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
        assert harness.metrics_for(bench, w["name"], "per_layer")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(harness.reader(m["name"]).read)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in [x["name"] for x in bench["workloads"]]
    for c in bench["configs"]:
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] \
            == c["name"]


def test_latency_readers_by_hand():
    record = {"latencies_s": [0.1 * i for i in range(1, 21)]}
    assert harness.reader("latency_p50_ms").read(record, None) \
        == pytest.approx(1050.0)
    # linear interpolation between the 19th and 20th of 20
    assert harness.reader("latency_p95_ms").read(record, None) \
        == pytest.approx(1905.0)
    assert harness.reader("latency_p95_ms").read({"latencies_s": []},
                                                None) is None


def test_reader_falls_back_to_the_metric_stem():
    assert harness.reader("mux_host_ms.rt") is not None
    with pytest.raises(harness.BenchError):
        harness.reader("no_such_metric.rt")


# ---------------- work and peaks ----------------

def test_mmse_split_work_by_hand():
    flops, nbytes = mmse_split.per_lane(nr_mmse.shapes(
        _cfg("nr100_mimo64x16")))
    # 6 m n^2 + 8 m n k + (2n)^3/3 + 2 (2n)^2 k at m=64, n=16, k=144
    assert flops == pytest.approx(98304 + 1179648 + 32768 / 3 + 294912)
    # four input planes and the (32 x 144) answer, float32
    assert nbytes == 4 * (2048 + 18432 + 4608)


def test_peaks_name_their_source_and_refuse_unknown_kinds():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
    pk = harness.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks("cpu")


def test_kernel_match_reads_names_and_stats():
    assert mmse_split.match("_unknown_.1", ["x tpu_custom_call y"])
    assert not mmse_split.match("copy.3", ["copy"])


def _summary(*ops):
    return {"ops": {name: {"s": s, "count": n, "stats": [name]}
                    for name, s, n in ops}}


def _record(jobs):
    return {"jobs_launched_in_window": jobs, "device_kind": "TPU v5 lite",
            "shapes": nr_mmse.shapes(_cfg("nr100_mimo64x16"))}


def test_roofline_counts_the_jobs_served_by_hand():
    # 3 launches of 8 lanes carried 17 jobs (the last one 1 job, 7 fillers)
    # in 30 us of kernel time; memory-bound: 100,352 bytes a job
    summary = _summary(("k.1 custom-call tpu_custom_call", 30e-6, 3),
                       ("copy.2 copy", 5e-6, 3))
    got = harness.reader("mmse_split_roofline.rt").read(_record(17), summary)
    assert got == pytest.approx(100.0 * 17 * 100_352 / 819e9 / 30e-6)
    assert harness.reader("mmse_split_roofline").read(_record(0),
                                                      summary) is None
    assert harness.reader("mmse_split_roofline").read(_record(17),
                                                      None) is None
    assert harness.reader("mmse_split_roofline").read(
        _record(17), _summary(("copy.2 copy", 5e-6, 3))) is None


def test_roofline_refuses_two_kernels_it_cannot_tell_apart():
    summary = _summary(("k.1 custom-call tpu_custom_call", 30e-6, 3),
                       ("k.2 custom-call tpu_custom_call", 9e-6, 1))
    with pytest.raises(harness.BenchError, match="named apart"):
        harness.reader("mmse_split_roofline").read(_record(17), summary)


# ---------------- job kinds ----------------

@pytest.mark.parametrize("kind,cfg,per_request", [
    (nr_mmse, NR_TINY, "prbs")], ids=["nr_mmse"])
def test_requests_are_deterministic_by_seed(kind, cfg, per_request):
    big = 2 ** 31 + 12345
    a = kind.make_request(cfg, np.random.default_rng([big, 0]))
    b = kind.make_request(cfg, np.random.default_rng([big, 0]))
    c = kind.make_request(cfg, np.random.default_rng([big, 1]))
    assert len(a) == cfg[per_request]
    for ja, jb in zip(a, b):
        for x, y in zip(ja, jb):
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][0], c[0][0])
    for job in a:
        assert tuple(x.shape for x in job) == kind.shapes(cfg)
        assert all(x.dtype == np.float32 for x in job)


def test_real_size_shapes():
    assert nr_mmse.shapes(_cfg("nr100_mimo64x16")) == (
        (64, 16), (64, 16), (64, 144), (64, 144))


@pytest.mark.parametrize("cfg_name,variant", [
    ("nr100_mimo64x16", "split_complex")])
def test_buckets_dispatch_to_the_stated_variant(cfg_name, variant):
    from repro import kernels as K
    cfg = _cfg(cfg_name)
    kind = harness.job_kind(cfg)
    shapes = kind.shapes(cfg)
    v = K.get(kind.PIPELINE).dispatch_key(shapes,
                                          (np.float32,) * len(shapes))
    assert v.name == variant == cfg["variant"]


def test_nr_reference_matches_the_program_oracle():
    from repro.kernels import ref
    args = nr_mmse.make_request(NR_TINY, np.random.default_rng(3))
    want = nr_mmse.reference(NR_TINY, args)
    planes = [np.stack([a[i] for a in args]) for i in range(4)]
    got = np.asarray(ref.mmse_equalize_split(*planes, sigma2=0.1))
    assert np.max(harness.rel_errors(got, want)) < 1e-5


@pytest.mark.parametrize("kind,cfg", [(nr_mmse, NR_TINY)],
                         ids=["nr_mmse"])
def test_control_is_the_reference_in_a_lower_precision(kind, cfg):
    args = kind.make_request(cfg, np.random.default_rng(6))
    err = harness.rel_errors(kind.control(cfg, args),
                             kind.reference(cfg, args))
    # close to the answer, but not to float32 rounding
    assert np.all(err < 1e-2) and np.max(err) > 1e-6


def test_rel_errors_flags_non_finite_answers():
    want = np.ones((2, 3, 3))
    got = want.copy()
    got[1, 0, 0] = np.nan
    assert list(harness.rel_errors(got, want)) == [0.0, np.inf]
