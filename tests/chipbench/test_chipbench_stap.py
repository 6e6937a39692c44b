"""The STAP cell's parts on the CPU: its configuration and entries, the
``stap_smi`` job kind's inputs, reference and control, the tiled
Cholesky's work and roofline reader, and one tiny CPI served through
the ``SolverMux`` at an n that 128 does not divide."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.jobs import stap_smi  # noqa: E402
from chipbench.work import chol_tiled  # noqa: E402

CELL = "stap.cpi_bulk"


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


@pytest.fixture(scope="module")
def cfg(bench):
    return harness.cell(bench, CELL)[1]


def _tiny(cfg, **over):
    """8 channels x 34 pulses: 272 complex DOF, so the real-embedded
    n = 544 is past the tiled threshold and not a multiple of 128."""
    tiny = dict(cfg, channels=8, pulses=34, training=544, range_cells=600,
                segments=3, doppler_bins=4, clutter_patches=64)
    return dict(tiny, **over)


# ---------------- configuration and entries ----------------

def test_configuration_is_kassper_data_set_1(bench, cfg):
    conf = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert conf["source"] == cfg["source"] and "KASSPER" in conf["source"]
    assert conf["reduced"] == cfg["reduced"] == []
    assert (cfg["channels"], cfg["pulses"]) == (11, 32)
    assert cfg["training"] == 2 * stap_smi.dof(cfg) == 704
    assert stap_smi.shapes(cfg) == ((704, 704), (704, 32))
    entry, _, traffic = harness.cell(bench, CELL)
    assert entry["chips"] == 1 and traffic["loop"] == "closed"
    assert (traffic["outstanding"], traffic["pool"]) == (2, 4)
    assert cfg["mux"]["lanes"] == cfg["segments"] == 8
    assert "deadline_s" not in cfg and cfg["priority"] == "hard"
    e2e = [m["name"] for m in harness.metrics_for(bench, CELL, "end_to_end")]
    layer = [m["name"] for m in harness.metrics_for(bench, CELL, "per_layer")]
    assert sorted(e2e) == ["setup_s", "solves_per_s"]
    assert layer == ["chol_tiled_roofline.cpi"]


def test_bucket_dispatches_to_tiled(cfg):
    from repro import kernels as K
    shapes = stap_smi.shapes(cfg)
    v = K.get(stap_smi.PIPELINE).dispatch_key(shapes, (np.float32,) * 2)
    assert v.name == cfg["variant"] == "tiled"
    assert v.run_shapes(shapes) == ((768, 768), (768, 32))


# ---------------- inputs, reference and control ----------------

def test_requests_are_deterministic_by_seed(cfg):
    tiny = _tiny(cfg)
    big = 2 ** 31 + 12345
    a = stap_smi.make_request(tiny, np.random.default_rng([big, 0]))
    b = stap_smi.make_request(tiny, np.random.default_rng([big, 0]))
    c = stap_smi.make_request(tiny, np.random.default_rng([big, 1]))
    assert len(a) == tiny["segments"]
    for ja, jb in zip(a, b):
        for x, y in zip(ja, jb):
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][0], c[0][0])
    for r, s in a:
        assert (r.shape, s.shape) == stap_smi.shapes(tiny)
        assert r.dtype == s.dtype == np.float32
        np.testing.assert_array_equal(r, r.T)      # the embedding is SPD
    # the segments' training windows overlap but are not the same
    assert not np.array_equal(a[0][0], a[1][0])


def test_reference_matches_the_real_embedded_solve(cfg):
    """The complex128 solve of the rebuilt complex system is the real
    solve of the embedding, in float64 through the program's oracle."""
    import jax
    from repro.kernels import ref
    tiny = _tiny(cfg, segments=2)
    args = stap_smi.make_request(tiny, np.random.default_rng(3))
    want = stap_smi.reference(tiny, args)
    with jax.enable_x64(True):
        got = np.asarray(ref.cholesky_solve(
            np.stack([a for a, _ in args]).astype(np.float64),
            np.stack([b for _, b in args]).astype(np.float64)))
    assert np.max(harness.rel_errors(got, want)) < 1e-8


def test_control_fails_the_limit_at_the_cells_size(cfg):
    args = stap_smi.make_request(cfg, np.random.default_rng([2 ** 31 + 9,
                                                             0]))
    err = harness.rel_errors(stap_smi.control(cfg, args),
                             stap_smi.reference(cfg, args))
    # close to the answer, and over the limit in every job
    assert np.all(err < 0.5) and np.all(err > cfg["limits"]["max_rel_err"])


# ---------------- work and roofline ----------------

def test_chol_tiled_work_by_hand():
    flops, nbytes = chol_tiled.per_lane(((704, 704), (704, 32)))
    # n^3/3 + 2 n^2 k at the job's n = 704, not the padded 768
    assert flops == pytest.approx(704 ** 3 / 3 + 31_719_424)
    assert flops == pytest.approx(148.02e6, rel=1e-4)
    # A and B read, the (704 x 32) answer written, float32
    assert nbytes == 4 * (495_616 + 2 * 22_528) == 2_162_688


def test_chol_tiled_matches_its_own_op_only():
    assert chol_tiled.match("cholesky_solve_tiled.1 custom-call "
                            "tpu_custom_call", [])
    assert chol_tiled.match("cholesky_solve_tiled custom-call "
                            "tpu_custom_call", [])
    # the padding around it names the entry point in its metadata only
    assert not chol_tiled.match(
        "pad_select_fusion fusion",
        ['op_name="jit(cholesky_solve_tiled)/tiled_pad/select_n"'])
    assert not chol_tiled.match("cholesky_solve_blocked.1 custom-call "
                                "tpu_custom_call", [])
    assert not chol_tiled.match("mmse_split.1 custom-call tpu_custom_call",
                                ["tpu_custom_call"])


def test_roofline_reader_by_hand(cfg):
    summary = {"ops": {
        "cholesky_solve_tiled.1 custom-call tpu_custom_call":
            {"s": 0.5, "count": 40, "stats": []},
        "pad_select_fusion fusion": {"s": 0.01, "count": 40, "stats": []}}}
    record = {"jobs_launched_in_window": 320, "device_kind": "TPU v5 lite",
              "shapes": stap_smi.shapes(cfg)}
    got = harness.reader("chol_tiled_roofline.cpi").read(record, summary)
    # memory-bound: 2,162,688 bytes a job at 819 GB/s
    assert got == pytest.approx(100.0 * 320 * 2_162_688 / 819e9 / 0.5)
    assert harness.reader("chol_tiled_roofline.cpi").read(record,
                                                          None) is None


# ---------------- served ----------------

def test_tiny_cpi_served_through_the_mux(cfg):
    """One CPI of 3 segments on 2 lanes at n = 544: both launches go to
    the tiled variant at 640, the answers meet the cell's limit, and the
    padded work is counted by hand: 4 lanes run at 640, 3 jobs at 544."""
    from repro.serve import ManualClock, SolverMux
    tiny = _tiny(cfg)
    args = stap_smi.make_request(tiny, np.random.default_rng(5))
    mux = SolverMux(lanes=2, clock=ManualClock())
    jobs = [mux.submit(stap_smi.PIPELINE, a, b, priority="hard")
            for a, b in args]
    mux.run()
    assert [j.state for j in jobs] == ["done"] * 3
    got = np.stack([j.out for j in jobs])
    err = harness.rel_errors(got, stap_smi.reference(tiny, args))
    assert np.max(err) <= tiny["limits"]["max_rel_err"]
    snap = mux.metrics()
    assert snap.pipelines[stap_smi.PIPELINE].dispatch_counts == {"tiled": 2}
    k = tiny["doppler_bins"]
    flops = lambda n: n ** 3 / 3 + 2 * n * n * k  # noqa: E731
    assert snap.total_pad_flops == pytest.approx(4 * flops(640)
                                                 - 3 * flops(544))

