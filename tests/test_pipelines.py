"""Fused solver pipelines vs oracles (shape/dtype/batch sweeps incl.
non-power-of-two partial-vector tails, paper Feature 3), registry-driven
auto-discovery checks, degenerate-input guard paths, inductive-domain
masking (no garbage-lane reads), and serving through SolverMux."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ref as ref
from repro import kernels as K
from repro.kernels.common import sample_spd
from repro.pipelines import (cholesky_solve_pallas, cholesky_solve_unfused,
                             expand_complex_channel, mmse_equalize_composed,
                             mmse_equalize_pallas, qr_solve_pallas,
                             qr_solve_unfused)
from repro.serve import SolverMux

from conftest import assert_close

RNG = np.random.default_rng(4321)

# paper data sizes 8..32, non-power-of-two included (partial vector tails)
SIZES = [8, 12, 16, 24, 32]


def spd(b, n, dtype=np.float32):
    return sample_spd(RNG, b, n).astype(dtype)


# ---------------- cholesky_solve ----------------

@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("b", [1, 3])
def test_cholesky_solve_sizes(n, b):
    a = spd(b, n)
    rhs = RNG.standard_normal((b, n, 4)).astype(np.float32)
    got = cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs))
    assert_close(got, ref.cholesky_solve(a, rhs), rtol=1e-4,
                 name=f"chol_solve{n}")


@pytest.mark.parametrize("m", [1, 2, 8])
def test_cholesky_solve_rhs_widths(m):
    a = spd(2, 16)
    rhs = RNG.standard_normal((2, 16, m)).astype(np.float32)
    got = cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs))
    assert_close(got, ref.cholesky_solve(a, rhs), rtol=1e-4, name=f"rhs{m}")


def test_cholesky_solve_returns_factor():
    a = spd(2, 12)
    rhs = RNG.standard_normal((2, 12, 1)).astype(np.float32)
    x, l = cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs),
                                 return_l=True)
    l = np.asarray(l)
    assert_close(l @ l.swapaxes(-1, -2), a, rtol=1e-4, name="LL^T")
    assert np.allclose(np.triu(l, 1), 0.0)


def test_cholesky_solve_fused_matches_unfused():
    """Fusion is a scheduling change, not a numeric one."""
    a = spd(3, 24)
    rhs = RNG.standard_normal((3, 24, 2)).astype(np.float32)
    fused = cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs))
    unfused = cholesky_solve_unfused(jnp.asarray(a), jnp.asarray(rhs))
    assert_close(fused, unfused, rtol=1e-4, name="fused-vs-unfused")


def test_cholesky_solve_bf16():
    a = spd(2, 16)
    rhs = RNG.standard_normal((2, 16, 2)).astype(np.float32)
    got = cholesky_solve_pallas(jnp.asarray(a, jnp.bfloat16),
                                jnp.asarray(rhs, jnp.bfloat16))
    assert_close(np.asarray(got, np.float32), ref.cholesky_solve(a, rhs),
                 rtol=8e-2, name="chol_solve-bf16")


# ---------------- qr_solve ----------------

@pytest.mark.parametrize("m,n", [(8, 8), (12, 8), (16, 12), (24, 16),
                                 (32, 32), (36, 24)])
def test_qr_solve_sizes(m, n):
    a = RNG.standard_normal((2, m, n)).astype(np.float32)
    b = RNG.standard_normal((2, m, 3)).astype(np.float32)
    got = qr_solve_pallas(jnp.asarray(a), jnp.asarray(b))
    assert_close(got, ref.qr_solve(a, b), rtol=1e-4, name=f"qr{m}x{n}")


def test_qr_solve_least_squares_residual():
    """For tall systems the residual must be orthogonal to range(A)."""
    a = RNG.standard_normal((2, 24, 12)).astype(np.float32)
    b = RNG.standard_normal((2, 24, 1)).astype(np.float32)
    x = np.asarray(qr_solve_pallas(jnp.asarray(a), jnp.asarray(b)))
    resid = a @ x - b
    assert np.abs(np.einsum("bmn,bmk->bnk", a, resid)).max() < 1e-3


def test_qr_solve_fused_matches_unfused():
    a = RNG.standard_normal((2, 20, 16)).astype(np.float32)
    b = RNG.standard_normal((2, 20, 2)).astype(np.float32)
    fused = qr_solve_pallas(jnp.asarray(a), jnp.asarray(b))
    unfused = qr_solve_unfused(jnp.asarray(a), jnp.asarray(b))
    assert_close(fused, unfused, rtol=1e-3, name="qr-fused-vs-unfused")


# ---------------- mmse_equalize ----------------

@pytest.mark.parametrize("n", SIZES)
def test_mmse_sizes(n):
    m = n + 4
    h = RNG.standard_normal((2, m, n)).astype(np.float32)
    y = RNG.standard_normal((2, m, 2)).astype(np.float32)
    got = mmse_equalize_pallas(jnp.asarray(h), jnp.asarray(y))
    assert_close(got, ref.mmse_equalize(h, y), rtol=1e-4, name=f"mmse{n}")


@pytest.mark.parametrize("batch", [1, 5, 8])
def test_mmse_batches(batch):
    h = RNG.standard_normal((batch, 16, 12)).astype(np.float32)
    y = RNG.standard_normal((batch, 16, 1)).astype(np.float32)
    got = mmse_equalize_pallas(jnp.asarray(h), jnp.asarray(y))
    assert_close(got, ref.mmse_equalize(h, y), rtol=1e-4,
                 name=f"mmse-b{batch}")


def test_mmse_fused_matches_composed():
    h = RNG.standard_normal((3, 20, 16)).astype(np.float32)
    y = RNG.standard_normal((3, 20, 2)).astype(np.float32)
    fused = mmse_equalize_pallas(jnp.asarray(h), jnp.asarray(y))
    composed = mmse_equalize_composed(jnp.asarray(h), jnp.asarray(y))
    assert_close(fused, composed, rtol=1e-4, name="mmse-fused-vs-composed")


def test_mmse_complex_expansion_recovers_symbols():
    """End-to-end 5G shape: noiseless complex channel, equalizer must
    invert it (sigma2 -> tiny regularization only)."""
    b, m, n = 4, 16, 12
    hr = RNG.standard_normal((b, m, n)).astype(np.float32)
    hi = RNG.standard_normal((b, m, n)).astype(np.float32)
    xr = RNG.standard_normal((b, n, 1)).astype(np.float32)
    xi = RNG.standard_normal((b, n, 1)).astype(np.float32)
    yr = hr @ xr - hi @ xi
    yi = hr @ xi + hi @ xr
    h, y = expand_complex_channel(jnp.asarray(hr), jnp.asarray(hi),
                                  jnp.asarray(yr), jnp.asarray(yi))
    xhat = np.asarray(mmse_equalize_pallas(h, y, sigma2=1e-6))
    want = np.concatenate([xr, xi], axis=1)
    assert_close(xhat, want, rtol=1e-3, name="complex-recovery")


# ---------------- registry-driven auto-discovery ----------------

def test_registry_has_kernels_and_pipelines():
    assert set(K.names(kind="pipeline")) == {"cholesky_solve", "qr_solve",
                                             "mmse_equalize", "pusch_fft",
                                             "pusch_chanest", "pusch_chain",
                                             "svd_factor", "svd_apply"}
    assert set(K.dag_names()) == {"pusch_receive", "svd_solve"}
    # every seed kernel is registered — the registry IS the import list
    assert {"cholesky", "trisolve", "qr", "svd", "gemm", "fir", "fft",
            "flash_attention", "ssm_scan"} <= set(K.names(kind="kernel"))


@pytest.mark.parametrize("name", sorted(K.names()))
def test_registry_kernel_matches_oracle(name):
    """Auto-discovered: every registered kernel/pipeline checks against
    its own oracle over its declared size sweep — adding a kernel to the
    registry adds it to this test with no edits here."""
    spec = K.get(name)
    rng = np.random.default_rng(99)
    for n in spec.sizes:
        args = spec.make_case(rng, n)
        got = jax.tree.leaves(spec.run_pallas(*args))
        want = jax.tree.leaves(spec.run_oracle(*args))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_close(np.asarray(g, np.float32), w, rtol=spec.rtol,
                         name=f"{name}@{n}")


def test_registry_streams_classify():
    """Stream descriptors attached to the registry reproduce the paper's
    capability classification: solver-family kernels are inductive (RI),
    dense/regular kernels rectangular (paper Q10)."""
    for name in ("cholesky", "trisolve", "qr", "cholesky_solve",
                 "qr_solve", "mmse_equalize"):
        s = K.get(name).stream(16)
        assert "I" in s.capability, name
        assert s.length() > 0
    for name in ("gemm", "fir", "fft", "ssm_scan"):
        assert set(K.get(name).stream(16).capability) == {"R"}, name


# ---------------- degenerate inputs (guard paths) ----------------

def test_cholesky_solve_singular_stays_finite():
    """Rank-deficient SPD (outer product): the eps pivot guard must keep
    every lane finite instead of spraying NaNs."""
    v = RNG.standard_normal((2, 16, 2)).astype(np.float32)
    a = v @ v.swapaxes(-1, -2)                   # rank 2 << 16
    rhs = RNG.standard_normal((2, 16, 3)).astype(np.float32)
    x = np.asarray(cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs)))
    assert np.isfinite(x).all()


def test_cholesky_solve_ill_conditioned_accuracy():
    """cond ~ 1e4 (above the deficiency threshold): still solves to loose
    tolerance (float32 limit)."""
    q, _ = np.linalg.qr(RNG.standard_normal((16, 16)))
    eig = np.geomspace(1.0, 1e-4, 16).astype(np.float32)
    a = (q * eig) @ q.T
    a = a[None].astype(np.float32)
    rhs = RNG.standard_normal((1, 16, 1)).astype(np.float32)
    x = np.asarray(cholesky_solve_pallas(jnp.asarray(a), jnp.asarray(rhs)))
    assert np.isfinite(x).all()
    assert_close(a @ x, rhs, rtol=2e-2, name="illcond-residual")


def test_qr_solve_rank_deficient_stays_finite():
    """Duplicate columns -> zero householder norm + zero R diagonal: both
    the tau=0 and the clamped-denominator guards fire."""
    col = RNG.standard_normal((2, 16, 1)).astype(np.float32)
    a = np.repeat(col, 8, axis=2)                # rank 1
    b = RNG.standard_normal((2, 16, 2)).astype(np.float32)
    x = np.asarray(qr_solve_pallas(jnp.asarray(a), jnp.asarray(b)))
    assert np.isfinite(x).all()


def test_qr_solve_exact_zero_pivot_stays_finite():
    """R with a hard-zero diagonal entry ([[0,1],[0,0]] pattern): the
    deficient component must be ZEROED, not divided by a clamped tiny
    pivot (which cascades to inf through the remaining rows)."""
    a = np.array([[[0.0, 1.0], [0.0, 0.0], [0.0, 0.0]]], np.float32)
    b = np.ones((1, 3, 1), np.float32)
    x = np.asarray(qr_solve_pallas(jnp.asarray(a), jnp.asarray(b)))
    assert np.isfinite(x).all()


def test_qr_solve_zero_matrix_stays_finite():
    a = np.zeros((1, 12, 8), np.float32)
    b = RNG.standard_normal((1, 12, 1)).astype(np.float32)
    x = np.asarray(qr_solve_pallas(jnp.asarray(a), jnp.asarray(b)))
    assert np.isfinite(x).all()


def test_mmse_zero_channel_stays_finite():
    """All-zero channel: G = sigma2 I, x = 0 — regularization only."""
    h = np.zeros((1, 16, 12), np.float32)
    y = RNG.standard_normal((1, 16, 1)).astype(np.float32)
    x = np.asarray(mmse_equalize_pallas(jnp.asarray(h), jnp.asarray(y)))
    assert np.isfinite(x).all()
    assert np.abs(x).max() < 1e-5


# ---------------- inductive-domain masking (paper F4) ----------------

def test_cholesky_solve_ignores_upper_triangle_garbage():
    """The fused solve reads ONLY the lower triangle (the inductive
    domain): NaN-poisoning the strict upper half must not change x."""
    a = spd(2, 16)
    rhs = RNG.standard_normal((2, 16, 2)).astype(np.float32)
    clean = np.asarray(cholesky_solve_pallas(jnp.asarray(a),
                                             jnp.asarray(rhs)))
    poisoned = a.copy()
    iu = np.triu_indices(16, k=1)
    poisoned[:, iu[0], iu[1]] = np.nan
    got = np.asarray(cholesky_solve_pallas(jnp.asarray(poisoned),
                                           jnp.asarray(rhs)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, clean, rtol=0, atol=0)


def test_trisolve_masked_lanes_never_read_garbage():
    """The seed trisolve kernel's masked AXPY: NaNs planted in the strict
    upper triangle of L (outside the inductive domain) must not leak."""
    from repro.kernels.trisolve import trisolve_pallas
    a = spd(2, 12)
    l = np.linalg.cholesky(a)
    b = RNG.standard_normal((2, 12, 2)).astype(np.float32)
    clean = np.asarray(trisolve_pallas(jnp.asarray(l), jnp.asarray(b)))
    lp = l.copy()
    iu = np.triu_indices(12, k=1)
    lp[:, iu[0], iu[1]] = 1e30            # garbage (inf-adjacent) lanes
    got = np.asarray(trisolve_pallas(jnp.asarray(lp), jnp.asarray(b)))
    np.testing.assert_allclose(got, clean, rtol=0, atol=0)


# ---------------- serving ----------------

def test_mux_serves_and_pads():
    """Jobs of mixed shapes, lane-pool padding: every job gets its own
    answer; padded identity lanes never contaminate real ones."""
    mux = SolverMux(lanes=4)
    jobs = []
    for n in (8, 8, 12):                  # 2 groups; both need padding
        a = spd(1, n)[0]
        b = RNG.standard_normal((n, 2)).astype(np.float32)
        jobs.append(mux.submit("cholesky_solve", a, b))
    done = mux.run()
    assert len(done) == 3 and mux.pending() == 0
    for j in jobs:
        a, b = j.args
        want = np.asarray(ref.cholesky_solve(a[None], b[None]))[0]
        assert_close(j.out, want, rtol=1e-4, name="mux-job")


def test_mux_matches_direct_batch():
    """One full lane group == a direct pallas call on the same stack."""
    mux = SolverMux(lanes=4)
    h = RNG.standard_normal((4, 16, 12)).astype(np.float32)
    y = RNG.standard_normal((4, 16, 1)).astype(np.float32)
    jobs = [mux.submit("mmse_equalize", h[i], y[i]) for i in range(4)]
    mux.run()
    direct = np.asarray(mmse_equalize_pallas(jnp.asarray(h),
                                             jnp.asarray(y)))
    np.testing.assert_allclose(np.stack([j.out for j in jobs]), direct,
                               rtol=0, atol=0)
