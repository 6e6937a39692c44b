"""PUSCH receiver stage kernels: the DAG-served pipeline's new stages.

The end-to-end 5G PUSCH uplink receive chain (arXiv:2210.09196) is a
producer/consumer pipeline — OFDM demod (FFT) feeds pilot-based channel
estimation feeds MMSE equalization — whose stages the serving stack
schedules as a DAG (``repro.kernels.DagSpec`` / ``SolverMux.submit_dag``).
This module holds the stage entry points that did not already exist as
registered pipelines:

``channel_estimate_pallas``
    Regularized least-squares channel estimation from pilots: given the
    known pilot block Xp (N, P) and its received observation Yp (M, P),
    solve (Xp Xp^T + ridge I) Z = Xp Yp^T and return H = Z^T (M, N) —
    a Gram + fused Cholesky chain per lane, the same VMEM-resident
    factor/substitution fusion as ``pipelines.mmse``.

``pusch_chain_pallas``
    The lane-resident fusion of channel-estimate -> MMSE equalize: one
    ``pallas_call`` whose grid cell estimates H from pilots and
    immediately consumes it for the data-symbol equalization — the
    estimated channel is handed from producer to consumer through
    VMEM/registers, never through HBM (the PR 1 fusion pattern applied
    ACROSS DAG stages).  Serving this entry instead of the two separate
    stages is the "stage-chained" mode the ``serve_slo/dag/*`` benchmark
    rows compare against stage-independent launches.

``pusch_fft_pallas``
    Stage adapter over the registered FFT kernel: per lane, A antenna
    rows of NF time samples -> a single stacked (2, A, NF) re/im
    frequency buffer (the serving stack moves ONE array per stage
    output, so the tuple-returning FFT is packed into planes).

``svd_factor_pallas`` / ``svd_apply_pallas``
    The non-wireless generality DAG: one-sided-Jacobi SVD packed into a
    single (M+N+1, N) factor buffer [U; V; s], then a ridge-regularized
    pseudo-inverse apply x = V diag(s / (s^2 + lam)) U^T b — two GEMMs
    and a scale, fused in one grid cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import dot, eye, interpret_default
from repro.kernels.fft import fft_pallas
from repro.kernels.svd import svd_pallas
from repro.pipelines.cholesky_solve import DEFAULT_EPS, chol_solve_inline

DEFAULT_RIDGE = 1e-3
DEFAULT_LAM = 1e-3


def _gram_plus_eye(x, shift: float):
    """x x^T + shift I, the regularized Gram of an (n, p) block."""
    return dot(x, x.T) + shift * eye(x.shape[0])


def _estimate_h(xp, yp, *, ridge: float, eps: float):
    """Regularized LS estimate H (m, n) from xp (n, p), yp (m, p)."""
    _, z = chol_solve_inline(_gram_plus_eye(xp, ridge), dot(xp, yp.T),
                             eps=eps)                   # (n, m)
    return z.T                                          # (m, n)


def _chanest_kernel(xp_ref, yp_ref, h_ref, *, ridge: float, eps: float):
    xp = xp_ref[0].astype(jnp.float32)
    yp = yp_ref[0].astype(jnp.float32)
    h = _estimate_h(xp, yp, ridge=ridge, eps=eps)
    h_ref[0] = h.astype(h_ref.dtype)


def channel_estimate_pallas(xp: jax.Array, yp: jax.Array, *,
                            ridge: float = DEFAULT_RIDGE,
                            eps: float = DEFAULT_EPS,
                            interpret: bool | None = None) -> jax.Array:
    """LS channel estimate.  xp: (B,N,P) known pilots, yp: (B,M,P)
    received pilots -> H (B,M,N)."""
    bsz, n, p = xp.shape
    b2, m, p2 = yp.shape
    assert bsz == b2 and p == p2, (xp.shape, yp.shape)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_chanest_kernel, ridge=ridge, eps=eps),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, p), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), yp.dtype),
        interpret=interpret,
        name="pusch_chanest",
    )(xp, yp)


def _pusch_chain_kernel(xp_ref, yp_ref, y_ref, x_ref, *, ridge: float,
                        sigma2: float, eps: float):
    xp = xp_ref[0].astype(jnp.float32)
    yp = yp_ref[0].astype(jnp.float32)
    y = y_ref[0].astype(jnp.float32)
    # stage 1: channel estimate — H never leaves VMEM
    h = _estimate_h(xp, yp, ridge=ridge, eps=eps)
    # stage 2: MMSE equalize consuming the just-produced H
    ht = h.T
    _, x = chol_solve_inline(_gram_plus_eye(ht, sigma2), dot(ht, y),
                             eps=eps)
    x_ref[0] = x.astype(x_ref.dtype)


def pusch_chain_pallas(xp: jax.Array, yp: jax.Array, y: jax.Array, *,
                       ridge: float = DEFAULT_RIDGE, sigma2: float = 0.1,
                       eps: float = DEFAULT_EPS,
                       interpret: bool | None = None) -> jax.Array:
    """Fused channel-estimate -> equalize.  xp: (B,N,P), yp: (B,M,P),
    y: (B,M,K) -> x (B,N,K), one pallas_call."""
    bsz, n, p = xp.shape
    _, m, _ = yp.shape
    b3, m2, k = y.shape
    assert bsz == b3 and m == m2, (xp.shape, yp.shape, y.shape)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_pusch_chain_kernel, ridge=ridge, sigma2=sigma2,
                          eps=eps),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n, p), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, p), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, k), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, k), y.dtype),
        interpret=interpret,
        name="pusch_chain",
    )(xp, yp, y)


def pusch_fft_pallas(xr: jax.Array, xi: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """OFDM demod stage adapter: (B, A, NF) time-domain re/im planes per
    antenna -> (B, 2, A, NF) stacked frequency planes.  The antenna axis
    is folded into the FFT kernel's batch (each row is one independent
    NF-point transform)."""
    bsz, a, nf = xr.shape
    fr, fi = fft_pallas(xr.reshape(bsz * a, nf), xi.reshape(bsz * a, nf),
                        interpret=interpret)
    return jnp.stack([fr.reshape(bsz, a, nf), fi.reshape(bsz, a, nf)],
                     axis=1)


def svd_factor_pallas(a: jax.Array, *, sweeps: int = 14,
                      interpret: bool | None = None) -> jax.Array:
    """SVD stage adapter: (B, M, N) -> packed factor buffer
    (B, M+N+1, N) = rows [U; V; s] (single-array stage output)."""
    u, s, v = svd_pallas(a, sweeps=sweeps, interpret=interpret)
    return jnp.concatenate([u, v, s[:, None, :]], axis=1)


def _svd_apply_kernel(f_ref, b_ref, x_ref, *, m: int, n: int,
                      lam: float):
    f = f_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    u = f[:m]                                           # (m, n)
    v = f[m:m + n]                                      # (n, n)
    s = f[m + n:m + n + 1].T                            # (n, 1)
    w = (s / (s * s + lam)) * dot(u.T, b)               # (n, k)
    x = dot(v, w)
    x_ref[0] = x.astype(x_ref.dtype)


def svd_apply_pallas(f: jax.Array, b: jax.Array, *,
                     lam: float = DEFAULT_LAM,
                     interpret: bool | None = None) -> jax.Array:
    """Ridge-regularized pseudo-inverse apply from packed SVD factors:
    x = V diag(s / (s^2 + lam)) U^T b.  f: (B, M+N+1, N), b: (B, M, K)
    -> (B, N, K).  Equals (A^T A + lam I)^{-1} A^T b, so the answer is
    invariant to the SVD's sign/order ambiguity."""
    bsz, mn1, n = f.shape
    b2, m, k = b.shape
    assert bsz == b2 and mn1 == m + n + 1, (f.shape, b.shape)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_svd_apply_kernel, m=m, n=n, lam=lam),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, mn1, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, k), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, k), b.dtype),
        interpret=interpret,
        name="svd_apply",
    )(f, b)
