"""SolverMux: registry-driven multi-pipeline serving — mixed-type
routing, shape-bucket grouping, deadline-aware flush ordering,
timeout/pressure partial flushes, registry-filler padding (every
registered pipeline, no contamination of real lanes), and the SLO
metrics snapshot."""
import numpy as np
import pytest

from repro import kernels as K
from repro.kernels import ref
from repro.kernels.common import sample_spd
from repro.serve import ManualClock, SolverMux, pad_group

from conftest import assert_close

RNG = np.random.default_rng(1234)


def chol_args(n, rng=RNG):
    return (sample_spd(rng, 1, n)[0],
            rng.standard_normal((n, 2)).astype(np.float32))


def tall_args(n, k=2, rng=RNG):
    """(m, n) tall matrix + (m, k) rhs — qr_solve / mmse_equalize shape."""
    m = n + 4
    return (rng.standard_normal((m, n)).astype(np.float32),
            rng.standard_normal((m, k)).astype(np.float32))


def oracle_of(job):
    """Single-lane registry-oracle answer for a SolveJob."""
    return K.get(job.pipeline).run_oracle_lane(*job.args)


# ---------------- mixed routing + batching (acceptance) ----------------

def test_mux_mixed_stream_batches_and_matches_oracles():
    """Interleaved cholesky/qr/mmse jobs at >= 2 distinct shapes each,
    one run(): every job gets its own oracle-matching answer, and
    batching actually happens (fewer grid launches than jobs)."""
    mux = SolverMux(lanes=4, clock=ManualClock())
    jobs = []
    for _ in range(4):                       # interleaved, never grouped
        for n in (8, 12):
            jobs.append(mux.submit("cholesky_solve", *chol_args(n)))
            jobs.append(mux.submit("qr_solve", *tall_args(n)))
            jobs.append(mux.submit("mmse_equalize", *tall_args(n)))
    done = mux.run()
    assert len(done) == len(jobs) == 24
    assert mux.pending() == 0
    for job in jobs:
        assert_close(job.out, oracle_of(job), rtol=1e-3,
                     name=f"mux-{job.pipeline}")
    snap = mux.metrics()
    assert snap.total_launches < snap.total_jobs == 24
    # 3 pipelines x 2 shapes x 4 jobs -> ceil(4/4) = 1 launch per bucket
    assert snap.total_launches == 6


def test_mux_routes_by_pipeline():
    mux = SolverMux(lanes=4, clock=ManualClock())
    j1 = mux.submit("cholesky_solve", *chol_args(8))
    j2 = mux.submit("qr_solve", *tall_args(8))
    mux.run()
    assert j1.pipeline == "cholesky_solve" and j2.pipeline == "qr_solve"
    per = mux.metrics().pipelines
    assert per["cholesky_solve"].jobs == 1
    assert per["qr_solve"].jobs == 1


def test_mux_rejects_non_pipeline_and_unknown():
    mux = SolverMux(lanes=4)
    with pytest.raises(ValueError):
        mux.submit("gemm", np.eye(8, dtype=np.float32))
    with pytest.raises(KeyError):
        mux.submit("no_such_pipeline", np.eye(8, dtype=np.float32))


def test_mux_options_bound_per_pipeline():
    """Per-pipeline options reach the served kernel (sigma2 here)."""
    mux = SolverMux(lanes=2, clock=ManualClock(),
                    options={"mmse_equalize": {"sigma2": 0.05}})
    h, y = tall_args(8)
    job = mux.submit("mmse_equalize", h, y)
    mux.run()
    want = np.asarray(ref.mmse_equalize(h[None], y[None], sigma2=0.05))[0]
    assert_close(job.out, want, rtol=1e-3, name="mmse-sigma2-option")


# ---------------- shape buckets ----------------

def test_mux_shape_buckets_never_mix():
    """Jobs of different shapes never share a grid launch; same-shape
    jobs do."""
    mux = SolverMux(lanes=4, clock=ManualClock())
    for _ in range(4):
        mux.submit("cholesky_solve", *chol_args(8))
    for _ in range(3):
        mux.submit("cholesky_solve", *chol_args(12))
    mux.run()
    snap = mux.metrics()
    assert snap.total_launches == 2
    by_shape = {l.shape: l for l in snap.launches}
    assert len(by_shape) == 2                 # one launch per shape bucket
    reals = sorted(l.real for l in snap.launches)
    assert reals == [3, 4]


def test_mux_rhs_width_is_part_of_bucket_key():
    """Same matrix size, different rhs width -> different buckets."""
    mux = SolverMux(lanes=4, clock=ManualClock())
    mux.submit("cholesky_solve", *chol_args(8))
    a, _ = chol_args(8)
    mux.submit("cholesky_solve", a,
               RNG.standard_normal((8, 5)).astype(np.float32))
    done = mux.run()
    assert mux.metrics().total_launches == 2
    for job in done:
        assert_close(job.out, oracle_of(job), rtol=1e-3, name="rhs-width")


# ---------------- deadline-aware flush policy ----------------

def test_mux_deadline_flush_ordering():
    """run() flushes the oldest-deadline bucket first; a no-deadline
    bucket goes last regardless of submission order."""
    mux = SolverMux(lanes=4, clock=ManualClock())
    mux.submit("qr_solve", *tall_args(8))                    # no deadline
    mux.submit("cholesky_solve", *chol_args(8), deadline=3.0)
    mux.submit("mmse_equalize", *tall_args(8), deadline=1.0)
    mux.submit("cholesky_solve", *chol_args(12), deadline=2.0)
    mux.run()
    order = [l.pipeline for l in mux.metrics().launches]
    assert order == ["mmse_equalize", "cholesky_solve",
                     "cholesky_solve", "qr_solve"]


def test_mux_poll_dispatches_full_groups_holds_partials():
    """poll(): a full lane group goes out immediately; a partial bucket
    with no expired deadline stays queued until run() drains it."""
    clk = ManualClock()
    mux = SolverMux(lanes=4, clock=clk)
    full = [mux.submit("cholesky_solve", *chol_args(8)) for _ in range(4)]
    part = mux.submit("cholesky_solve", *chol_args(12))
    done = mux.poll()
    assert sorted(id(j) for j in done) == sorted(id(j) for j in full)
    assert mux.pending() == 1 and part.out is None
    clk.advance(100.0)                 # no max_wait, no deadline: holds
    assert mux.poll() == []
    assert mux.run() == [part]
    assert_close(part.out, oracle_of(part), rtol=1e-3, name="partial")


def test_mux_remainder_reranks_behind_older_bucket():
    """A bucket whose oldest jobs were chunked away must re-rank by its
    remaining jobs: the leftover (newer) job flushes AFTER an older
    bucket submitted in between."""
    mux = SolverMux(lanes=2, clock=ManualClock())
    for _ in range(2):                          # bucket A: full group
        mux.submit("cholesky_solve", *chol_args(8))
    older = mux.submit("cholesky_solve", *chol_args(12))   # bucket B
    leftover = mux.submit("cholesky_solve", *chol_args(8))  # A again
    mux.poll()                                  # dispatches A's full pair
    assert mux.pending() == 2
    done = mux.run()
    assert [j.seq for j in done] == [older.seq, leftover.seq]


def test_mux_poll_flushes_expired_deadline():
    clk = ManualClock()
    mux = SolverMux(lanes=4, clock=clk)
    job = mux.submit("mmse_equalize", *tall_args(8), deadline=1.0)
    assert mux.poll() == []                      # deadline not reached
    clk.advance(1.5)
    assert mux.poll() == [job]
    assert job.out is not None


def test_mux_poll_flushes_aged_partials_after_max_wait():
    clk = ManualClock()
    mux = SolverMux(lanes=4, max_wait=0.010, clock=clk)
    job = mux.submit("qr_solve", *tall_args(8))
    clk.advance(0.005)
    assert mux.poll() == []                      # younger than max_wait
    clk.advance(0.006)
    assert mux.poll() == [job]


def test_mux_pressure_flushes_oldest_bucket_first():
    """Pool pressure flushes partial buckets (oldest deadline/arrival
    first) until the pool drops below the threshold."""
    clk = ManualClock()
    mux = SolverMux(lanes=8, pressure=4, clock=clk)
    older = [mux.submit("cholesky_solve", *chol_args(8))
             for _ in range(3)]
    newer = [mux.submit("cholesky_solve", *chol_args(12))
             for _ in range(2)]
    done = mux.poll()                  # queued 5 >= 4: flush oldest bucket
    assert sorted(id(j) for j in done) == sorted(id(j) for j in older)
    assert mux.pending() == 2          # relieved: newer bucket survives
    assert all(j.out is None for j in newer)
    mux.run()


# ---------------- registry-filler padding ----------------

@pytest.mark.parametrize("name", sorted(K.names(kind="pipeline")))
def test_mux_padded_lanes_never_contaminate(name):
    """EVERY registered pipeline: a 3-job group padded to the 4-lane pool
    via the spec's declared filler returns real-lane results identical to
    the oracle — the padding lane is benign by construction."""
    spec = K.get(name)
    assert spec.filler is not None, f"{name} must declare a filler"
    rng = np.random.default_rng(5)
    n = spec.sizes[0]
    batched = [np.asarray(a) for a in spec.make_case(rng, n)]
    extra = [np.asarray(a) for a in spec.make_case(rng, n)]
    mux = SolverMux(lanes=4, clock=ManualClock())
    jobs = [mux.submit(name, *[a[i] for a in batched]) for i in range(2)]
    jobs.append(mux.submit(name, *[a[0] for a in extra]))
    mux.run()
    launches = mux.metrics().launches
    assert len(launches) == 1 and launches[0].padded == 1
    for job in jobs:
        assert_close(job.out, oracle_of(job), rtol=spec.rtol,
                     name=f"pad-{name}")


def test_mux_pads_square_rhs_qr_without_corruption():
    """Acceptance check for the removed shape heuristic: a qr_solve batch
    whose rhs is SQUARE (m x m) — ambiguous under the old 'square 3-D arg
    => add identity' rule — pads cleanly from the registry filler."""
    rng = np.random.default_rng(6)
    n, m = 8, 12
    mux = SolverMux(lanes=4, clock=ManualClock())
    jobs = []
    for _ in range(3):                          # 3 jobs -> 1 padded lane
        a = rng.standard_normal((m, n)).astype(np.float32)
        b = rng.standard_normal((m, m)).astype(np.float32)   # square rhs
        jobs.append(mux.submit("qr_solve", a, b))
    mux.run()
    assert mux.metrics().launches[0].padded == 1
    for job in jobs:
        a, b = job.args
        want = np.asarray(ref.qr_solve(a[None], b[None]))[0]
        assert_close(job.out, want, rtol=1e-3, name="square-rhs-pad")


def test_pad_group_requires_declared_filler():
    """No filler declared -> padding is an error, never a guess."""
    stacked = [np.zeros((3, 8, 8), np.float32)]
    with pytest.raises(ValueError, match="filler"):
        pad_group(K.get("gemm"), stacked, lanes=4)


# ---------------- SLO metrics snapshot ----------------

def test_mux_metrics_snapshot_deterministic():
    """On a manual clock the whole snapshot is exact: counts, lane
    utilization/waste, p50/p99 latency, and windowed throughput."""
    clk = ManualClock()
    mux = SolverMux(lanes=4, clock=clk)
    mux.submit("cholesky_solve", *chol_args(8))
    clk.advance(0.25)
    mux.submit("cholesky_solve", *chol_args(8))
    clk.advance(0.25)                  # latencies: 0.5 and 0.25 s
    mux.run()
    st = mux.metrics()["cholesky_solve"]
    assert st.jobs == 2 and st.launches == 1
    assert st.lanes_dispatched == 4 and st.lanes_padded == 2
    assert st.lane_utilization == pytest.approx(0.5)
    assert st.padded_lane_waste == pytest.approx(0.5)
    assert st.latency.count == 2
    assert st.latency.max == pytest.approx(0.5)
    assert st.latency.p50 == pytest.approx(0.375)   # midpoint of 2 samples
    assert st.latency.p99 == pytest.approx(0.4975, rel=1e-3)
    # window = first submit (t=0) .. last finish (t=0.5) -> 2 jobs / 0.5 s
    assert st.throughput == pytest.approx(4.0)


def test_mux_metrics_reset():
    mux = SolverMux(lanes=2, clock=ManualClock())
    mux.submit("cholesky_solve", *chol_args(8))
    mux.run()
    assert mux.metrics().total_jobs == 1
    mux.reset_metrics()
    snap = mux.metrics()
    assert snap.total_jobs == 0 and snap.total_launches == 0
