"""Tiled solves at an n that 128 does not divide.

With no ``bs`` given, the tiled entry points run every n in 128-wide
column slabs (the only width Mosaic DMAs out of an HBM ref), padding an
n that 128 does not divide up to whole slabs on the device: ``[[A, 0],
[0, I]]`` against ``[b; 0]`` for the Cholesky and QR solves, zero channel
columns for MMSE.  These tests hold the padded runs to the oracles, the
padded unknowns to exactly 0, the deficiency thresholds to the job's own
columns, and the registry's and the mux's accounting of the work spent
on padding to a count by hand.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.kernels import ref
from repro.pipelines import (cholesky_solve_tiled, mmse_equalize_tiled,
                             qr_solve_tiled, tiled_padded_n)
from repro.pipelines.cholesky_solve import pad_identity, pad_rows
from repro.serve import ManualClock, SolverMux

from conftest import assert_close
from strategies import spd_system, tall_system

PIPELINES = ("cholesky_solve", "qr_solve", "mmse_equalize")
TILED = {"cholesky_solve": cholesky_solve_tiled, "qr_solve": qr_solve_tiled,
         "mmse_equalize": mmse_equalize_tiled}
ORACLE = {"cholesky_solve": ref.cholesky_solve, "qr_solve": ref.qr_solve,
          "mmse_equalize": ref.mmse_equalize}


def _case(name, seed, n, k=2):
    if name == "cholesky_solve":
        return spd_system(seed, 1, n, k=k)
    return tall_system(seed, 1, n + 16, n, k=k)


def _chol_flops(n, k):
    return n ** 3 / 3.0 + 2.0 * n * n * k


@pytest.mark.parametrize("name", PIPELINES)
@pytest.mark.parametrize("n", [160, 416, 544])
def test_default_slabs_match_the_oracle(name, n):
    """n = 160, 416, 544 run at 256, 512 and 640 in 128-wide slabs and
    answer the job's own n rows."""
    a, b = _case(name, seed=n, n=n)
    got = TILED[name](jnp.asarray(a), jnp.asarray(b))
    assert got.shape == (1, n, 2)
    assert_close(got, ORACLE[name](jnp.asarray(a), jnp.asarray(b)),
                 rtol=1e-3, name=f"padded-tiled-{name} n={n}")


def test_padded_n_is_whole_slabs():
    assert [tiled_padded_n(n) for n in (160, 512, 544, 704, 1024, 1888)] \
        == [256, 512, 640, 768, 1024, 1920]


def test_pad_identity_and_rows_by_hand():
    a = np.arange(1.0, 7.0, dtype=np.float32).reshape(1, 3, 2)
    got = np.asarray(pad_identity(jnp.asarray(a), 2))
    want = np.zeros((1, 5, 4), np.float32)
    want[0, :3, :2] = a[0]
    want[0, 3, 2] = want[0, 4, 3] = 1.0
    np.testing.assert_array_equal(got, want)
    got = np.asarray(pad_rows(jnp.asarray(a), 2))
    np.testing.assert_array_equal(got, np.concatenate(
        [a, np.zeros((1, 2, 2), np.float32)], axis=1))


@pytest.mark.parametrize("name", PIPELINES)
def test_padded_unknowns_solve_to_zero(name):
    """The padded system run as it is, at bs = 128: its first n unknowns
    are the job's answer and the padded ones are 0 — exactly for the
    Cholesky and MMSE factors, whose rank-1 updates of the padded block
    are by exact zeros; to rounding for QR, whose compact-WY panel
    products mix the padded reflectors with the real ones in the
    slab they share."""
    n, p = 160, 96
    a, b = _case(name, seed=3, n=n)
    if name == "mmse_equalize":       # the entry point asks m >= its n
        a, b = tall_system(3, 1, n + p + 16, n)
    a, b = jnp.asarray(a), jnp.asarray(b)
    if name == "mmse_equalize":
        big = (jnp.pad(a, ((0, 0), (0, 0), (0, p))), b)
    else:
        big = (pad_identity(a, p), pad_rows(b, p))
    x = np.asarray(TILED[name](*big, bs=128))
    if name == "qr_solve":
        assert np.abs(x[:, n:]).max() <= 1e-6 * np.abs(x).max()
    else:
        assert np.all(x[:, n:] == 0.0)
    assert_close(x[:, :n], ORACLE[name](a, b), rtol=1e-3, name=name)


@pytest.mark.parametrize("name", ["cholesky_solve", "qr_solve"])
def test_threshold_reads_only_the_jobs_own_columns(name):
    """A system scaled down far below the padded unit pivots: were the
    padding counted in the deficiency threshold, every real column
    would read as deficient and solve to 0."""
    n, scale = 160, 1e-8
    a, b = _case(name, seed=4, n=n)
    got = TILED[name](jnp.asarray(a * scale), jnp.asarray(b * scale))
    assert_close(got, ORACLE[name](jnp.asarray(a), jnp.asarray(b)),
                 rtol=1e-3, name=f"scaled-{name}")


@pytest.mark.parametrize("name,n,run", [
    ("cholesky_solve", 704, ((768, 768), (768, 32))),
    ("qr_solve", 704, ((784, 768), (784, 32))),
    ("mmse_equalize", 704, ((720, 768), (720, 32))),
    ("cholesky_solve", 512, ((512, 512), (512, 32)))])
def test_tiled_variant_reports_the_shapes_it_runs_at(name, n, run):
    mat = (n, n) if name == "cholesky_solve" else (n + 16, n)
    shapes = (mat, (mat[0], 32))
    v = K.get(name).dispatch_key(shapes, (np.float32,) * 2)
    assert v.name == "tiled"
    assert v.run_shapes(shapes) == run


def test_pad_flops_by_hand():
    v = K.get("cholesky_solve").dispatch_key(((704, 704), (704, 32)),
                                             (np.float32,) * 2)
    shapes = ((704, 704), (704, 32))
    job, run = _chol_flops(704, 32), _chol_flops(768, 32)
    # a full launch: padded rows only, 21.6% of the work run
    assert v.pad_flops(shapes, 8, 8) == pytest.approx(8 * (run - job))
    assert (job, run) == (pytest.approx(148_023_978.67), 188_743_680.0)
    assert v.pad_flops(shapes, 8, 8) / (8 * run) == pytest.approx(
        0.2157, abs=1e-4)
    # 5 jobs and 3 filler lanes: the fillers count whole
    assert v.pad_flops(shapes, 5, 8) == pytest.approx(8 * run - 5 * job)
    # a variant that pads no rows counts its filler lanes alone
    base = K.get("cholesky_solve").dispatch_key(((8, 8), (8, 2)),
                                                (np.float32,) * 2)
    assert base.pad_flops(((8, 8), (8, 2)), 3, 4) == pytest.approx(
        _chol_flops(8, 2))


def _small_jobs(count, n=8):
    a, b = spd_system(9, count, n, k=2)
    return [(a[i], b[i]) for i in range(count)]


@pytest.mark.parametrize("engine", ["mux"])
def test_snapshot_totals_the_pad_flops(engine):
    """3 jobs on 2 lanes, one filler lane: it is counted whole, and the
    snapshot sums the launches' records."""
    mux = SolverMux(lanes=2, clock=ManualClock())
    for a, b in _small_jobs(3):
        mux.submit("cholesky_solve", a, b)
    mux.run()
    snap = mux.metrics()
    assert sum(lr.padded for lr in snap.launches) == 1
    assert snap.total_pad_flops == sum(lr.pad_flops for lr in snap.launches)
    assert snap.total_pad_flops == pytest.approx(_chol_flops(8, 2))
