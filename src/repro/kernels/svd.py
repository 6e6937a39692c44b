"""Batched one-sided Jacobi SVD (paper Fig. 6 right).

The pair loop (p, q) with q in [p+1, n) is itself an inductive (RI)
iteration domain — the inner fori_loop's lower bound depends on the outer
iterator, exactly the stream shape REVEL encodes with a stretch parameter.
The rotation-parameter region (div/sqrt chains) is the non-critical
dataflow; the two-column rotations are the critical vector region.

Works on (B, M, N) with M >= N; returns U (B,M,N), S (B,N), V (B,N,N)
with A ~= U * S @ V^T (singular values unsorted; ops.py sorts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import eye, interpret_default, put_col, take_col


def _rotate_pair(mat, p, q, cs, sn):
    colp = take_col(mat, p)
    colq = take_col(mat, q)
    mat = put_col(mat, p, cs * colp - sn * colq)
    return put_col(mat, q, sn * colp + cs * colq)


def _svd_kernel(a_ref, u_ref, s_ref, v_ref, *, n: int, sweeps: int):
    a = a_ref[0].astype(jnp.float32)
    v = eye(n)

    def pair_body(p, q, av):
        a, v = av
        colp = take_col(a, p)
        colq = take_col(a, q)
        # ---- non-critical point region: rotation parameters ----
        alpha = jnp.sum(colp * colp, axis=0, keepdims=True)
        beta = jnp.sum(colq * colq, axis=0, keepdims=True)
        gamma = jnp.sum(colp * colq, axis=0, keepdims=True)
        small = jnp.abs(gamma) <= 1e-12 * jnp.sqrt(alpha * beta) + 1e-30
        zeta = (beta - alpha) / (2.0 * jnp.where(small, 1.0, gamma))
        t = jnp.sign(zeta) / (jnp.abs(zeta) + jnp.sqrt(1.0 + zeta * zeta))
        t = jnp.where(zeta == 0.0, 1.0, t)
        cs = jax.lax.rsqrt(1.0 + t * t)
        sn = cs * t
        cs = jnp.where(small, 1.0, cs)
        sn = jnp.where(small, 0.0, sn)
        # ---- critical region: rotate columns of A and V ----
        a = _rotate_pair(a, p, q, cs, sn)
        v = _rotate_pair(v, p, q, cs, sn)
        return a, v

    def sweep(_, av):
        def outer(p, av):
            # inductive inner bound: q in [p+1, n) — RI domain
            return jax.lax.fori_loop(
                p + 1, n, lambda q, av_: pair_body(p, q, av_), av)
        return jax.lax.fori_loop(0, n - 1, outer, av)

    a, v = jax.lax.fori_loop(0, sweeps, sweep, (a, v))
    s = jnp.sqrt(jnp.sum(a * a, axis=0, keepdims=True))     # (1, n)
    u = a / jnp.maximum(s, 1e-30)
    u_ref[0] = u.astype(u_ref.dtype)
    s_ref[0] = s.astype(s_ref.dtype)
    v_ref[0] = v.astype(v_ref.dtype)


def svd_pallas(a: jax.Array, *, sweeps: int = 12,
               interpret: bool | None = None):
    b, m, n = a.shape
    assert m >= n
    if interpret is None:
        interpret = interpret_default()
    u, s, v = pl.pallas_call(
        functools.partial(_svd_kernel, n=n, sweeps=sweeps),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            # unit middle axis: a (1, n) block of a (B, n) array breaks
            # the TPU's (8, 128) block rule, a (1, 1, n) block does not
            pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m, n), a.dtype),
            jax.ShapeDtypeStruct((b, 1, n), a.dtype),
            jax.ShapeDtypeStruct((b, n, n), a.dtype),
        ],
        interpret=interpret,
        name="svd",
    )(a)
    return u, s[:, 0], v
