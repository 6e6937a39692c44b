"""Performance variants + registry dispatch: split-complex MMSE vs the
complex oracle (property-tested, hypothesis-fuzzed when available),
blocked Cholesky/QR equality against the unblocked fused kernels across
block-size/shape sweeps, the model-FLOP win of the split path (HLO
dot-flops counter), and dispatch routing through registry, engine, and
mux (a mixed-size trace must land each bucket on the expected variant).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels as K
from repro.kernels import ref
from repro.kernels.common import sample_spd
from repro.pipelines import (cholesky_solve_blocked, cholesky_solve_pallas,
                             expand_complex_channel, mmse_equalize_pallas,
                             mmse_equalize_split_pallas, qr_solve_blocked,
                             qr_solve_pallas)
from repro.roofline.hlo_costs import analyze_hlo
from repro.serve import ManualClock, SolverMux

from conftest import assert_close
from strategies import channel_planes, floats, fuzzed, integers, spd_system

RNG = np.random.default_rng(777)


# ---------------- split-complex MMSE vs the complex oracle ----------------
# Property: for ANY complex system (any m >= n, k, sigma2), the split
# re/im kernel matches the complex64 jnp oracle to fp32 tolerance.  The
# deterministic grid always runs; hypothesis widens the shape/sigma space.

def _check_split_matches_complex_oracle(n, m_extra, k, sigma2, seed):
    hr, hi, yr, yi = [jnp.asarray(p) for p in channel_planes(
        seed, 2, n + m_extra, n, k=k)]
    got = mmse_equalize_split_pallas(hr, hi, yr, yi, sigma2=sigma2)
    want = ref.mmse_equalize_split(hr, hi, yr, yi, sigma2=sigma2)
    assert_close(got, want, rtol=1e-3,
                 name=f"split-mmse n={n} m={n + m_extra} k={k} s={sigma2}")


@pytest.mark.parametrize("n,m_extra,k", [(2, 0, 1), (8, 4, 2), (12, 4, 1),
                                         (16, 0, 3), (24, 8, 2)])
@pytest.mark.parametrize("sigma2", [1e-3, 0.1, 1.0])
def test_split_mmse_matches_complex_oracle(n, m_extra, k, sigma2):
    _check_split_matches_complex_oracle(n, m_extra, k, sigma2, seed=n + k)


@fuzzed(max_examples=10, n=integers(2, 10), m_extra=integers(0, 6),
        k=integers(1, 3), sigma2=floats(1e-3, 2.0),
        seed=integers(0, 2 ** 16))
def test_split_mmse_matches_complex_oracle_fuzzed(n, m_extra, k,
                                                  sigma2, seed):
    _check_split_matches_complex_oracle(n, m_extra, k, sigma2, seed)


def test_split_mmse_equals_expansion_path():
    """The split kernel assembles the SAME real-embedded 2n x 2n system
    the [[Re,-Im],[Im,Re]] expansion builds — answers agree to rounding."""
    b, m, n, k = 3, 20, 16, 2
    hr, hi = [jnp.asarray(RNG.standard_normal((b, m, n))
                          .astype(np.float32)) for _ in range(2)]
    yr, yi = [jnp.asarray(RNG.standard_normal((b, m, k))
                          .astype(np.float32)) for _ in range(2)]
    h, y = expand_complex_channel(hr, hi, yr, yi)
    split = mmse_equalize_split_pallas(hr, hi, yr, yi, sigma2=0.1)
    expanded = mmse_equalize_pallas(h, y, sigma2=0.1)
    assert_close(split, expanded, rtol=1e-4, name="split-vs-expansion")


def test_split_mmse_zero_channel_stays_finite():
    hr = jnp.zeros((1, 16, 12), jnp.float32)
    yr = jnp.asarray(RNG.standard_normal((1, 16, 1)).astype(np.float32))
    x = np.asarray(mmse_equalize_split_pallas(hr, hr, yr, yr, sigma2=0.1))
    assert np.isfinite(x).all()
    assert np.abs(x).max() < 1e-5


# ---------------- split-complex model-FLOP acceptance ----------------

def test_split_mmse_halves_model_flops():
    """Acceptance: at equal (m, n, k) the split kernel performs <= 0.55x
    the model FLOPs of the real-expansion kernel, measured by the HLO
    dot-flops counter on the LOWERED Pallas kernels themselves (the
    fused solve chain contributes no dot ops in either, so this isolates
    the Gram + matched-filter GEMM work: 6mn^2+8mnk vs 16mn^2+8mnk)."""
    from functools import partial
    for m, n, k in [(20, 16, 2), (36, 32, 1)]:
        hr, hi = [jnp.asarray(RNG.standard_normal((2, m, n))
                              .astype(np.float32)) for _ in range(2)]
        yr, yi = [jnp.asarray(RNG.standard_normal((2, m, k))
                              .astype(np.float32)) for _ in range(2)]
        h, y = expand_complex_channel(hr, hi, yr, yi)
        split_flops = analyze_hlo(
            jax.jit(partial(mmse_equalize_split_pallas, sigma2=0.1,
                            interpret=True))
            .lower(hr, hi, yr, yi).compile().as_text())["flops"]
        exp_flops = analyze_hlo(
            jax.jit(partial(mmse_equalize_pallas, sigma2=0.1,
                            interpret=True))
            .lower(h, y).compile().as_text())["flops"]
        assert split_flops > 0 and exp_flops > 0
        ratio = split_flops / exp_flops
        assert ratio <= 0.55, (m, n, k, ratio)
        # and the counter sees exactly the kernels' model dot counts
        assert split_flops == 2 * (6 * m * n * n + 8 * m * n * k)
        assert exp_flops == 2 * (16 * m * n * n + 8 * m * n * k)


# ---------------- blocked Cholesky: equality sweeps ----------------

def _check_blocked_chol_equals_unblocked(n, bs, rhs, seed):
    a, b = [jnp.asarray(p) for p in spd_system(seed, 2, n, k=rhs)]
    blocked = cholesky_solve_blocked(a, b, bs=bs)
    unblocked = cholesky_solve_pallas(a, b)
    assert_close(blocked, unblocked, rtol=1e-4,
                 name=f"chol-blocked n={n} bs={bs}")


@pytest.mark.parametrize("bs", [32, 64])
@pytest.mark.parametrize("n", [128, 256])
def test_blocked_cholesky_equals_unblocked(n, bs):
    """Acceptance sweep: blocking is a schedule change, not a numeric
    one — n=256 with bs in {32, 64} must match the fused kernel."""
    _check_blocked_chol_equals_unblocked(n, bs, rhs=3, seed=n + bs)


@pytest.mark.parametrize("rhs", [1, 5])
def test_blocked_cholesky_rhs_widths(rhs):
    _check_blocked_chol_equals_unblocked(128, 32, rhs=rhs, seed=rhs)


def test_blocked_cholesky_matches_oracle():
    a = jnp.asarray(sample_spd(RNG, 2, 128))
    b = jnp.asarray(RNG.standard_normal((2, 128, 2)).astype(np.float32))
    got = cholesky_solve_blocked(a, b)
    assert_close(got, ref.cholesky_solve(a, b), rtol=1e-3,
                 name="chol-blocked-oracle")


def test_blocked_cholesky_singular_stays_finite():
    """The eps pivot guard must survive blocking: a rank-deficient SPD
    matrix keeps every lane finite."""
    v = RNG.standard_normal((1, 128, 5)).astype(np.float32)
    a = jnp.asarray(v @ v.swapaxes(-1, -2))          # rank 5 << 128
    b = jnp.asarray(RNG.standard_normal((1, 128, 2)).astype(np.float32))
    x = np.asarray(cholesky_solve_blocked(a, b, bs=32))
    assert np.isfinite(x).all()


# ---------------- blocked QR: equality sweeps ----------------

def _check_blocked_qr_equals_unblocked(m, n, bs, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((2, m, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((2, m, 2)).astype(np.float32))
    blocked = qr_solve_blocked(a, b, bs=bs)
    unblocked = qr_solve_pallas(a, b)
    assert_close(blocked, unblocked, rtol=1e-3,
                 name=f"qr-blocked m={m} n={n} bs={bs}")


@pytest.mark.parametrize("bs", [32, 64])
@pytest.mark.parametrize("m,n", [(132, 128), (160, 128)])
def test_blocked_qr_equals_unblocked(m, n, bs):
    _check_blocked_qr_equals_unblocked(m, n, bs, seed=m + bs)


def test_blocked_qr_256_least_squares_residual():
    """n=256: the blocked solution's residual is orthogonal to range(A)
    (the defining property of the least-squares answer)."""
    a = RNG.standard_normal((1, 260, 256)).astype(np.float32)
    b = RNG.standard_normal((1, 260, 1)).astype(np.float32)
    x = np.asarray(qr_solve_blocked(jnp.asarray(a), jnp.asarray(b), bs=64))
    resid = a @ x - b
    corr = np.abs(np.einsum("bmn,bmk->bnk", a, resid)).max()
    assert corr / np.abs(b).max() < 2e-2            # fp32, n=256 scale


# ---------------- registry dispatch ----------------

def test_dispatch_routes_by_shape_and_arity():
    spec = K.get("cholesky_solve")
    small = spec.make_case(np.random.default_rng(0), 16)
    assert spec.dispatch(*small).name == "base"
    big = spec.make_case(np.random.default_rng(0), 256)
    assert spec.dispatch(*big).name == "blocked"
    # non-tiling sizes stay on base (the blocked panels need n % 32 == 0)
    odd = spec.make_case(np.random.default_rng(0), 136)
    assert spec.dispatch(*odd).name == "base"

    mmse = K.get("mmse_equalize")
    h, y = mmse.make_case(np.random.default_rng(0), 12)
    assert mmse.dispatch(h, y).name == "base"
    hr, hi, yr, yi = (np.asarray(h),) * 2 + (np.asarray(y),) * 2
    assert mmse.dispatch(hr, hi, yr, yi).name == "split_complex"


@pytest.mark.parametrize("name,variant", [
    (spec.name, v.name)
    for spec in K.specs(kind="pipeline") for v in spec.variants])
def test_registry_variant_matches_oracle(name, variant):
    """Auto-discovered: every registered variant checks against its own
    oracle (or the spec's) over its declared sizes, with dispatch
    actually selecting it — adding a variant adds it here with no
    edits."""
    spec = K.get(name)
    var = next(v for v in spec.variants if v.name == variant)
    rng = np.random.default_rng(321)
    make = var.make_case or spec.make_case
    oracle = var.oracle or spec.run_oracle
    for n in (var.sizes or spec.sizes[:1]):
        args = make(rng, n)
        assert spec.dispatch(*args).name == variant, (name, variant, n)
        got = jax.tree.leaves(var.fn(*args))
        want = jax.tree.leaves(oracle(*args))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert_close(np.asarray(g, np.float32), w, rtol=1e-3,
                         name=f"{name}/{variant}@{n}")


def test_kernels_without_variants_dispatch_to_base():
    spec = K.get("gemm")
    args = spec.make_case(np.random.default_rng(0), 16)
    v = spec.dispatch(*args)
    assert v.name == "base" and v.fn is spec.pallas


# ---------------- serving: buckets land on the expected variant ----------

def test_mux_mixed_trace_dispatches_each_bucket_to_expected_variant():
    """A mixed-size, mixed-arity trace through the SolverMux: the n=8
    bucket serves from base, the n=128 bucket from blocked, 4-plane MMSE
    jobs from split_complex — per-launch variant records and the
    dispatch_counts metric prove it, and every answer still matches the
    dispatch-aware registry oracle."""
    rng = np.random.default_rng(11)
    mux = SolverMux(lanes=2, clock=ManualClock())
    jobs = []
    for _ in range(2):
        jobs.append(mux.submit("cholesky_solve",
                               sample_spd(rng, 1, 8)[0],
                               rng.standard_normal((8, 2))
                               .astype(np.float32)))
        jobs.append(mux.submit("cholesky_solve",
                               sample_spd(rng, 1, 128)[0],
                               rng.standard_normal((128, 2))
                               .astype(np.float32)))
        m, n = 16, 12
        jobs.append(mux.submit("mmse_equalize",
                               *[rng.standard_normal(s)
                                 .astype(np.float32)
                                 for s in ((m, n), (m, n), (m, 1),
                                           (m, 1))]))
        jobs.append(mux.submit("qr_solve",
                               rng.standard_normal((132, 128))
                               .astype(np.float32),
                               rng.standard_normal((132, 1))
                               .astype(np.float32)))
    done = mux.run()
    assert len(done) == len(jobs)
    for job in jobs:
        want = K.get(job.pipeline).run_oracle_lane(*job.args)
        assert_close(job.out, want, rtol=2e-3,
                     name=f"mux-{job.pipeline}-{job.args[0].shape}")

    by_shape = {(l.pipeline, l.shape[0][0]): l.variant
                for l in mux.metrics().launches}
    assert by_shape[("cholesky_solve", (8, 8))] == "base"
    assert by_shape[("cholesky_solve", (128, 128))] == "blocked"
    assert by_shape[("mmse_equalize", (16, 12))] == "split_complex"
    assert by_shape[("qr_solve", (132, 128))] == "blocked"

    snap = mux.metrics()
    assert snap["cholesky_solve"].dispatch_counts == {"base": 1,
                                                      "blocked": 1}
    assert snap["mmse_equalize"].dispatch_counts == {"split_complex": 1}
    assert snap["qr_solve"].dispatch_counts == {"blocked": 1}


def test_mux_pads_split_complex_bucket_from_variant_filler():
    """A partial split-complex bucket pads from the VARIANT's declared
    4-plane filler (the spec's 2-arg filler cannot describe it)."""
    rng = np.random.default_rng(12)
    mux = SolverMux(lanes=4, clock=ManualClock())
    m, n = 12, 8
    job = mux.submit("mmse_equalize",
                     *[rng.standard_normal(s).astype(np.float32)
                       for s in ((m, n), (m, n), (m, 2), (m, 2))])
    mux.run()
    launch = mux.metrics().launches[0]
    assert launch.padded == 3 and launch.variant == "split_complex"
    want = K.get("mmse_equalize").run_oracle_lane(*job.args)
    assert_close(job.out, want, rtol=1e-3, name="split-padded")


def test_mux_dispatches_blocked():
    mux = SolverMux(lanes=2)
    rng = np.random.default_rng(13)
    jobs = [mux.submit("cholesky_solve",
                       sample_spd(rng, 1, 128)[0],
                       rng.standard_normal((128, 2)).astype(np.float32))
            for _ in range(2)]
    mux.run()
    assert mux.metrics()["cholesky_solve"].dispatch_counts == \
        {"blocked": 1}
    for j in jobs:
        want = K.get("cholesky_solve").run_oracle_lane(*j.args)
        assert_close(j.out, want, rtol=1e-3, name="mux-blocked")


# ---------------- FFT chunked twiddle table ----------------

def test_fft_chunked_twiddles_match_dense_layout():
    """The compact table packs stage s at offset 2**s - 1 with exactly
    the w_span^off values the old dense (stages x n/2) layout repeated."""
    from repro.kernels.fft import fft_tables
    n = 64
    rev, wre, wim = fft_tables(n)
    assert wre.shape == (n - 1,)
    for s in range(int(np.log2(n))):
        half = 1 << s
        for off in range(half):
            ang = -2.0 * np.pi * off / (half << 1)
            assert np.isclose(wre[half - 1 + off], np.cos(ang))
            assert np.isclose(wim[half - 1 + off], np.sin(ang))
    # bit-reversal unchanged
    assert rev[1] == n // 2 and rev[n - 1] == n - 1


def test_fft_1024_point_matches_oracle():
    """The paper's 1024-point size, unlocked by the chunked table."""
    from repro.kernels.fft import fft_pallas
    xr = jnp.asarray(RNG.standard_normal((2, 1024)).astype(np.float32))
    xi = jnp.asarray(RNG.standard_normal((2, 1024)).astype(np.float32))
    gr, gi = fft_pallas(xr, xi)
    wr, wi = ref.fft(xr, xi)
    assert_close(gr, wr, rtol=1e-3, name="fft1024-re")
    assert_close(gi, wi, rtol=1e-3, name="fft1024-im")
