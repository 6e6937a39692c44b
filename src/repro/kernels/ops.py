"""Public kernel API — jit'd wrappers with backend dispatch.

backend=None  -> pallas on TPU, xla elsewhere (production default)
backend='pallas' -> the Pallas kernel (interpret=True off-TPU: validation)
backend='xla' -> pure-jnp path (CPU fallback)

The xla paths are *not* the naive oracles from ref.py: they are the fused
FGOP formulations (same region fusion, same masking) expressed in jnp, so
fused-vs-naive can be compared on any backend.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.common import resolve_backend, round_up
from repro.kernels.cholesky import cholesky_pallas
from repro.kernels.trisolve import trisolve_pallas
from repro.kernels.qr import qr_pallas
from repro.kernels.svd import svd_pallas
from repro.kernels.gemm import gemm_pallas
from repro.kernels.fir import fir_pallas
from repro.kernels.fft import fft_pallas
from repro.kernels.attention import flash_attention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

__all__ = ["cholesky", "trisolve", "qr", "svd", "gemm", "fir", "fft",
           "flash_attention", "ssm_scan"]


# ---------------- factorizations ----------------

@partial(jax.jit, static_argnames=("backend",))
def cholesky(a: jax.Array, *, backend: str | None = None) -> jax.Array:
    if resolve_backend(backend) == "pallas":
        return cholesky_pallas(a)
    return ref.cholesky(a)


@partial(jax.jit, static_argnames=("backend", "lower"))
def trisolve(l: jax.Array, b: jax.Array, *, lower: bool = True,
             backend: str | None = None) -> jax.Array:
    if resolve_backend(backend) == "pallas":
        return trisolve_pallas(l, b, lower=lower)
    return ref.trisolve(l, b, lower=lower)


@partial(jax.jit, static_argnames=("backend",))
def qr(a: jax.Array, *, backend: str | None = None):
    if resolve_backend(backend) == "pallas":
        return qr_pallas(a)
    return ref.qr(a)


@partial(jax.jit, static_argnames=("backend", "sweeps", "sort"))
def svd(a: jax.Array, *, sweeps: int = 12, sort: bool = True,
        backend: str | None = None):
    """One-sided Jacobi SVD: returns (U, S, V), A ~= U*S @ V^T."""
    if resolve_backend(backend) == "pallas":
        u, s, v = svd_pallas(a, sweeps=sweeps)
    else:
        u, s, v = _svd_xla(a, sweeps=sweeps)
    if sort:
        order = jnp.argsort(-s, axis=-1)
        u = jnp.take_along_axis(u, order[:, None, :], axis=2)
        s = jnp.take_along_axis(s, order, axis=1)
        v = jnp.take_along_axis(v, order[:, None, :], axis=2)
    return u, s, v


def _svd_xla(a: jax.Array, *, sweeps: int):
    """Fused jacobi in plain jnp (vmapped over batch)."""

    def one(a0):
        m, n = a0.shape
        v0 = jnp.eye(n, dtype=jnp.float32)

        def pair(p, q, av):
            a, v = av
            colp = jax.lax.dynamic_slice(a, (0, p), (m, 1))[:, 0]
            colq = jax.lax.dynamic_slice(a, (0, q), (m, 1))[:, 0]
            alpha = jnp.sum(colp * colp)
            beta = jnp.sum(colq * colq)
            gamma = jnp.sum(colp * colq)
            small = jnp.abs(gamma) <= 1e-12 * jnp.sqrt(alpha * beta) + 1e-30
            zeta = (beta - alpha) / (2.0 * jnp.where(small, 1.0, gamma))
            t = jnp.sign(zeta) / (jnp.abs(zeta)
                                  + jnp.sqrt(1.0 + zeta * zeta))
            t = jnp.where(zeta == 0.0, 1.0, t)
            cs = jax.lax.rsqrt(1.0 + t * t)
            sn = cs * t
            cs = jnp.where(small, 1.0, cs)
            sn = jnp.where(small, 0.0, sn)

            def rot(mat):
                cp = jax.lax.dynamic_slice(mat, (0, p), (mat.shape[0], 1))
                cq = jax.lax.dynamic_slice(mat, (0, q), (mat.shape[0], 1))
                mat = jax.lax.dynamic_update_slice(
                    mat, cs * cp - sn * cq, (0, p))
                return jax.lax.dynamic_update_slice(
                    mat, sn * cp + cs * cq, (0, q))

            return rot(a), rot(v)

        def sweep(_, av):
            return jax.lax.fori_loop(
                0, n - 1,
                lambda p, av_: jax.lax.fori_loop(
                    p + 1, n, lambda q, av__: pair(p, q, av__), av_),
                av)

        a1, v1 = jax.lax.fori_loop(0, sweeps, sweep,
                                   (a0.astype(jnp.float32), v0))
        s = jnp.sqrt(jnp.sum(a1 * a1, axis=0))
        u = a1 / jnp.maximum(s, 1e-30)[None, :]
        return u.astype(a0.dtype), s.astype(a0.dtype), v1.astype(a0.dtype)

    return jax.vmap(one)(a)


# ---------------- dense / DSP ----------------

@partial(jax.jit, static_argnames=("backend", "bm", "bn", "bk"))
def gemm(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
         bk: int = 128, backend: str | None = None) -> jax.Array:
    if resolve_backend(backend) == "pallas":
        m, k = x.shape
        _, n = y.shape
        mp = round_up(m, min(bm, max(m, 8)))
        np_ = round_up(n, min(bn, max(n, 8)))
        kp = round_up(k, min(bk, max(k, 8)))
        xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
        yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
        return gemm_pallas(xp, yp, bm=min(bm, mp), bn=min(bn, np_),
                           bk=min(bk, kp))[:m, :n]
    return ref.gemm(x, y)


@partial(jax.jit, static_argnames=("backend", "bo"))
def fir(x: jax.Array, h: jax.Array, *, bo: int = 256,
        backend: str | None = None) -> jax.Array:
    """Centro-symmetric FIR, valid mode: y[i] = sum_j h[j] x[i+j]."""
    if resolve_backend(backend) == "pallas":
        n, = x.shape
        m, = h.shape
        out = n - m + 1
        bo = min(bo, out)
        pad = round_up(out, bo) - out
        xp = jnp.pad(x, (0, pad))
        return fir_pallas(xp, h, bo=bo)[:out]
    return ref.fir(x, h)


@partial(jax.jit, static_argnames=("backend",))
def fft(x_re: jax.Array, x_im: jax.Array, *, backend: str | None = None):
    if resolve_backend(backend) == "pallas":
        return fft_pallas(x_re, x_im)
    return ref.fft(x_re, x_im)


# ---------------- LM-side ----------------

@partial(jax.jit, static_argnames=("backend", "causal", "scale", "bq",
                                   "bkv"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = 128, bkv: int = 128,
                    backend: str | None = None) -> jax.Array:
    if resolve_backend(backend) == "pallas":
        return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      bq=bq, bkv=bkv)
    return ref.mha(q, k, v, causal=causal, scale=scale)


@partial(jax.jit, static_argnames=("backend", "chunk"))
def ssm_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
             chunk: int = 128, backend: str | None = None):
    """x: (B,S,H,P), a: (B,S,H), b/c: (B,S,N) shared-across-heads or
    (B,S,H,N) per-head -> y (B,S,H,P), h (B,H,N,P).

    (Time-major-per-head relayout for the kernel happens inside.)
    """
    if resolve_backend(backend) == "pallas":
        xk = jnp.moveaxis(x, 1, 2)            # (B,H,S,P)
        ak = jnp.moveaxis(a, 1, 2)            # (B,H,S)
        bk = b if b.ndim == 3 else jnp.moveaxis(b, 1, 2)
        ck = c if c.ndim == 3 else jnp.moveaxis(c, 1, 2)
        y, hf = ssm_scan_pallas(xk, ak, bk, ck, chunk=chunk)
        return jnp.moveaxis(y, 1, 2), hf
    return _ssm_chunked_xla(x, a, b, c, chunk=chunk)


def _ssm_chunked_xla(x, a, b, c, *, chunk: int):
    """Chunked SSD in plain jnp: same math as the kernel, scan over
    chunks (the ordered dependence is the scan carry)."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    per_head = b.ndim == 4
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    xc = x.reshape(bs, nc, chunk, h, p)
    ac = a.reshape(bs, nc, chunk, h)
    bshape = (bs, nc, chunk, h, n) if per_head else (bs, nc, chunk, n)
    bc = b.reshape(bshape)
    cc = c.reshape(bshape)

    ii = jnp.arange(chunk)[:, None]
    jj = jnp.arange(chunk)[None, :]
    tri = (jj <= ii)

    def step(hprev, t):
        xt, at, bt, ct = t                     # (B,cs,H,P),(B,cs,H),...
        la = jnp.cumsum(jnp.log(jnp.maximum(at, 1e-20)), axis=1)  # (B,cs,H)
        if per_head:
            g = jnp.einsum("bihn,bjhn->bijh", ct, bt)     # (B,i,j,H)
        else:
            g = jnp.einsum("bin,bjn->bij", ct, bt)[..., None]
        ldec = jnp.exp(la[:, :, None, :] - la[:, None, :, :])     # (B,i,j,H)
        m = jnp.where(tri[None, :, :, None], g * ldec, 0.0)
        y = jnp.einsum("bijh,bjhp->bihp", m, xt)
        if per_head:
            y = y + jnp.exp(la)[..., None] * jnp.einsum(
                "bihn,bhnp->bihp", ct, hprev)
        else:
            y = y + jnp.exp(la)[..., None] * jnp.einsum(
                "bin,bhnp->bihp", ct, hprev)
        total = la[:, -1, :]                                      # (B,H)
        dec = jnp.exp(total[:, None, :] - la)                     # (B,cs,H)
        if per_head:
            bw = bt * dec[..., None]                              # (B,cs,H,N)
        else:
            bw = bt[..., None, :] * dec[..., None]                # (B,cs,H,N)
        hnew = jnp.exp(total)[:, :, None, None] * hprev + jnp.einsum(
            "bjhn,bjhp->bhnp", bw, xt)
        return hnew, y

    h0 = jnp.zeros((bs, h, n, p), jnp.float32)
    xs = (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(ac, 1, 0),
          jnp.moveaxis(bc, 1, 0), jnp.moveaxis(cc, 1, 0))
    hf, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bs, s, h, p).astype(x.dtype)
    return y, hf.astype(x.dtype)
