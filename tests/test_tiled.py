"""HBM-scale tiled solver variants: equality-vs-oracle sweeps, the
tiled Cholesky's factor against NumPy's and its error at the STAP
cell's conditioning, VMEM working-set accounting (per-cell O(n*bs),
never O(n^2)), rank-deficiency pivot-guard behavior at tile boundaries,
F4 masking (NaN-poisoned upper triangle), dispatch routing at registry
and mux level, and hypothesis fuzzing via the shared strategies harness.

The n in {512, 1024} x bs in {64, 128} interpret-mode sweeps are marked
``slow`` (the scheduled CI job runs them); tier-1 keeps the midrange
shapes plus the no-compute dispatch assertions for the big buckets.
"""
import importlib
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.kernels import ref
from repro.pipelines import (cholesky_solve_pallas, cholesky_solve_tiled,
                             mmse_equalize_blocked, mmse_equalize_tiled,
                             mmse_tiled_vmem_floats, qr_solve_pallas,
                             qr_solve_tiled, qr_tiled_vmem_floats,
                             tiled_vmem_floats)
from repro.serve import ManualClock, SolverMux

from conftest import assert_close
from strategies import fuzzed, integers, sampled, spd_system, tall_system

PIPELINES = ("cholesky_solve", "qr_solve", "mmse_equalize")


def _tiled_case(name, seed, n, bs_k=2):
    if name == "cholesky_solve":
        return spd_system(seed, 1, n, k=bs_k)
    return tall_system(seed, 1, n + 16, n, k=bs_k)


def _run_tiled(name, a, b, bs):
    fn = {"cholesky_solve": cholesky_solve_tiled,
          "qr_solve": qr_solve_tiled,
          "mmse_equalize": mmse_equalize_tiled}[name]
    return fn(jnp.asarray(a), jnp.asarray(b), bs=bs)


def _oracle(name, a, b):
    fn = {"cholesky_solve": ref.cholesky_solve,
          "qr_solve": ref.qr_solve,
          "mmse_equalize": ref.mmse_equalize}[name]
    return fn(jnp.asarray(a), jnp.asarray(b))


# ---------------- equality vs oracle ----------------

@pytest.mark.parametrize("name", PIPELINES)
@pytest.mark.parametrize("n,bs", [(128, 32), (256, 64)])
def test_tiled_matches_oracle_midrange(name, n, bs):
    """Tier-1 shapes: data-tiling is a schedule/residency change, not a
    numeric one — the tiled chain matches the jnp oracle."""
    a, b = _tiled_case(name, seed=n + bs, n=n)
    got = _run_tiled(name, a, b, bs=bs)
    assert_close(got, _oracle(name, a, b), rtol=1e-3,
                 name=f"tiled-{name} n={n} bs={bs}")


@pytest.mark.slow
@pytest.mark.parametrize("name", PIPELINES)
@pytest.mark.parametrize("n", [512, 1024])
@pytest.mark.parametrize("bs", [64, 128])
def test_tiled_matches_oracle_large(name, n, bs):
    """The HBM-scale sweep (scheduled CI): n in {512, 1024} x bs in
    {64, 128}, every pipeline, interpret mode."""
    a, b = _tiled_case(name, seed=n + bs, n=n)
    got = _run_tiled(name, a, b, bs=bs)
    assert_close(got, _oracle(name, a, b), rtol=2e-3,
                 name=f"tiled-{name} n={n} bs={bs}")


def _tiled_factor(a, b, bs):
    """The factor the tiled Cholesky leaves in its HBM work buffer, for
    one lane ``a`` (1, n, n) that ``bs`` tiles."""
    cs = importlib.import_module("repro.pipelines.cholesky_solve")
    thr = np.maximum(cs.DEFAULT_EPS * a.diagonal(axis1=1, axis2=2).max(-1),
                     1e-30).astype(np.float32)
    _, l = cs._tiled_solve_and_factor(jnp.asarray(thr), jnp.asarray(a),
                                      jnp.asarray(b), bs=bs, interpret=True)
    return np.asarray(l)[0]


@pytest.mark.parametrize("n,bs", [(256, 64), (768, 128)])
def test_tiled_cholesky_factor_matches_numpy(n, bs):
    """The factor the kernel leaves in its HBM work buffer — each panel
    blocked: the ordered loop on the diagonal block, the rows below it
    by a GEMM against the block's inverse — is NumPy's Cholesky factor,
    zero above the diagonal."""
    a, b = spd_system(n + bs, 1, n, k=2)
    l = _tiled_factor(a, b, bs)
    assert not np.triu(l, 1).any()
    assert_close(l, np.linalg.cholesky(a[0].astype(np.float64)),
                 rtol=1e-5, name=f"factor n={n} bs={bs}")


def test_tiled_cholesky_meets_the_stap_limit():
    """One job of the STAP cell (KASSPER Data Set 1: n = 704, padded to
    768, loaded condition number about 5e5) on one lane: the tiled
    solve's error against the job kind's complex128 reference stays
    under the configuration's limit."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import harness
    from chipbench.jobs import stap_smi
    cfg = dict(harness.cell(harness.load_benchmark(), "stap.cpi_bulk")[1],
               segments=1)
    args = stap_smi.make_request(cfg, np.random.default_rng([2 ** 31 + 18,
                                                             0]))
    (a, b), = args
    got = cholesky_solve_tiled(jnp.asarray(a[None]), jnp.asarray(b[None]))
    err = harness.rel_errors(np.asarray(got), stap_smi.reference(cfg, args))
    assert np.max(err) < cfg["limits"]["max_rel_err"], err


@fuzzed(max_examples=6, n_tiles=integers(2, 4), bs=sampled(32, 64),
        seed=integers(0, 2 ** 16))
def test_tiled_cholesky_fuzzed(n_tiles, bs, seed):
    """Property: for ANY tiling (tile count, block size, seed) the tiled
    solve matches the single-block fused kernel."""
    n = n_tiles * bs
    a, b = spd_system(seed, 1, n, k=2)
    got = cholesky_solve_tiled(jnp.asarray(a), jnp.asarray(b), bs=bs)
    want = cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(b))
    assert_close(got, want, rtol=1e-3, name=f"fuzz n={n} bs={bs}")


@fuzzed(max_examples=4, n_tiles=integers(2, 3), bs=sampled(32, 64),
        seed=integers(0, 2 ** 16))
def test_tiled_qr_fuzzed(n_tiles, bs, seed):
    n = n_tiles * bs
    a, b = tall_system(seed, 1, n + 8, n, k=2)
    got = qr_solve_tiled(jnp.asarray(a), jnp.asarray(b), bs=bs)
    want = qr_solve_pallas(jnp.asarray(a), jnp.asarray(b))
    assert_close(got, want, rtol=2e-3, name=f"fuzz-qr n={n} bs={bs}")


# ---------------- F4 masking: only the lower triangle is read ----------

def test_tiled_cholesky_ignores_poisoned_upper_triangle():
    """NaN-poisoning the strict upper triangle must not change the
    answer: the tiled chain, like the fused kernel, only ever reads the
    lower triangle (paper Feature 4's implicit masking)."""
    n = 256
    a, b = spd_system(5, 1, n, k=2)
    want = cholesky_solve_tiled(jnp.asarray(a), jnp.asarray(b), bs=64)
    ap = a.copy()
    ap[0][np.triu_indices(n, 1)] = np.nan
    got = cholesky_solve_tiled(jnp.asarray(ap), jnp.asarray(b), bs=64)
    assert np.isfinite(np.asarray(got)).all()
    assert_close(got, want, rtol=1e-6, name="poisoned-upper")


# ---------------- VMEM working set: O(n*bs), not O(n^2) ----------------

def test_tiled_vmem_working_set_is_linear_in_n():
    """Doubling n at fixed bs doubles (not quadruples) the per-cell
    working set, and at n = 1024 the per-cell footprint is far below the
    O(n^2) a whole-matrix block would need — the declared scratch/block
    accounting the kernels enforce at call time."""
    for fn, args_small, args_big in [
            (tiled_vmem_floats, (512, 128, 2), (1024, 128, 2)),
            (qr_tiled_vmem_floats, (528, 512, 128, 2),
             (1040, 1024, 128, 2)),
            (mmse_tiled_vmem_floats, (528, 512, 128, 2),
             (1040, 1024, 128, 2))]:
        small, big = fn(*args_small), fn(*args_big)
        assert big <= 2.1 * small, (fn.__name__, small, big)
    n = 1024
    whole_matrix = n * n                       # the blocked kernels' cost
    assert tiled_vmem_floats(n, 128, 2) < 0.4 * whole_matrix
    assert mmse_tiled_vmem_floats(n + 16, n, 128, 2) < 0.7 * whole_matrix


def test_tiled_rejects_over_budget_shapes():
    """The call-time VMEM guard is real: a shape whose slabs alone
    exceed the budget is refused instead of silently compiled.  The
    guard fires on static shapes, so eval_shape exercises it without
    materializing the gigabyte-scale operands."""
    import functools
    import jax
    huge = 16384                               # 3*n*bs*4B > 14 MiB
    a = jax.ShapeDtypeStruct((1, huge, huge), jnp.float32)
    b = jax.ShapeDtypeStruct((1, huge, 2), jnp.float32)
    with pytest.raises(AssertionError):
        jax.eval_shape(functools.partial(cholesky_solve_tiled, bs=128),
                       a, b)


# ---------------- pivot guards at tile boundaries ----------------

@pytest.mark.parametrize("n,bs,rank", [
    pytest.param(256, 64, 40, id="40"),
    pytest.param(256, 64, 100, id="100"),
    pytest.param(256, 64, 129, id="129"),
    pytest.param(512, 128, 200, id="n512-bs128-200")])
def test_tiled_cholesky_deficiency_across_tile_boundaries(n, bs, rank):
    """Rank-deficient SPD input whose numerical rank ends inside the
    first, second, and third tile (bs=64), and in the middle of the
    second at bs=128, so that the deficient columns' rows below the
    diagonal block come out of the panel's GEMM: every lane stays
    finite, each deficient column of the factor (unit pivot) is exactly
    zero below it, and for a CONSISTENT right-hand side (b in range(A))
    the guarded solve still satisfies A x ~= b — the solution on the
    deficient subspace is not unique, so elementwise equality with the
    fused kernel is not a property; the residual is."""
    a, _ = spd_system(rank, 1, n, k=2, rank=rank)
    rng = np.random.default_rng(rank + 1)
    b = (a @ rng.standard_normal((1, n, 2))).astype(np.float32)
    got = np.asarray(cholesky_solve_tiled(jnp.asarray(a),
                                          jnp.asarray(b), bs=bs))
    assert np.isfinite(got).all()
    resid = np.abs(a @ got - b).max() / np.abs(b).max()
    assert resid < 1e-3, (rank, resid)
    l = _tiled_factor(a, b, bs)
    unit = np.flatnonzero(np.diag(l) == 1.0)
    assert unit.size > 0 and not np.tril(l, -1)[:, unit].any(), (rank, unit)


@pytest.mark.parametrize("col", [10, 70, 130])
def test_tiled_qr_deficient_column_in_any_panel(col):
    """A zeroed (numerically dependent) column inside panel 0, 1, and 2
    (bs=64): tau=0 reflector + zeroed solution component keep the tiled
    solve finite, matching the unblocked kernel's guard."""
    n = 192
    a, b = tall_system(col, 1, n + 8, n, k=2, deficient_col=col)
    got = qr_solve_tiled(jnp.asarray(a), jnp.asarray(b), bs=64)
    assert np.isfinite(np.asarray(got)).all()
    want = qr_solve_pallas(jnp.asarray(a), jnp.asarray(b))
    assert_close(got, want, rtol=2e-3, name=f"qr-deficient-col{col}")
    assert abs(np.asarray(got)[0, col]).max() < 1e-5


# ---------------- dispatch routing ----------------

@pytest.mark.parametrize("name", PIPELINES)
@pytest.mark.parametrize("n", [512, 1024, 1888, 2048])
def test_dispatcher_picks_tiled_for_hbm_buckets(name, n):
    """Registry routing for the n >= 512 shape buckets (no kernel runs:
    this is the pure dispatch decision serving uses per bucket).
    n = 1888 (% 64 != 0 but % 32 == 0) must route to tiled too — any
    n % 32 == 0 shape falling back to a whole-matrix VMEM kernel at
    this scale would OOM a real core."""
    spec = K.get(name)
    mat = (n, n) if name == "cholesky_solve" else (n + 16, n)
    key = (mat, (mat[0], 2))
    v = spec.dispatch_key(key, (np.float32, np.float32))
    assert v.name == "tiled", (name, n, v.name)
    from repro.pipelines import tiled_padded_n
    # the wrapper runs it in whole 128-wide slabs, padding under 128
    assert tiled_padded_n(n) % 128 == 0 and 0 <= tiled_padded_n(n) - n < 128
    # and the midrange/base buckets are untouched by the new variant
    small = ((24, 24), (24, 2)) if name == "cholesky_solve" \
        else ((28, 24), (28, 2))
    assert spec.dispatch_key(small, (np.float32,) * 2).name == "base"


def test_mmse_blocked_alias_is_tiled():
    """The ROADMAP's 'Blocked MMSE Gram' name resolves to the shipped
    tiled kernel."""
    assert mmse_equalize_blocked is mmse_equalize_tiled


@pytest.mark.slow
def test_mux_serves_hbm_bucket_from_tiled_variant():
    """End to end through the SolverMux: n=512 jobs of all three
    pipelines land on the tiled variant (dispatch_counts + per-launch
    variant records prove it) and still match the registry oracle."""
    mux = SolverMux(lanes=2, clock=ManualClock())
    jobs = []
    a, b = spd_system(0, 1, 512, k=2)
    jobs.append(mux.submit("cholesky_solve", a[0], b[0]))
    a, b = tall_system(1, 1, 528, 512, k=2)
    jobs.append(mux.submit("qr_solve", a[0], b[0]))
    h, y = tall_system(2, 1, 528, 512, k=2)
    jobs.append(mux.submit("mmse_equalize", h[0], y[0]))
    done = mux.run()
    assert len(done) == len(jobs)
    snap = mux.metrics()
    for name in PIPELINES:
        assert snap[name].dispatch_counts == {"tiled": 1}, (
            name, snap[name].dispatch_counts)
    for job in jobs:
        want = K.get(job.pipeline).run_oracle_lane(*job.args)
        assert_close(job.out, want, rtol=2e-3,
                     name=f"mux-tiled-{job.pipeline}")
