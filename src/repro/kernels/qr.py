"""Batched Householder QR (paper Fig. 6 left).

Per outer column k: the householder region (norm + rsqrt — non-critical
point/vector flow producing tau and v) feeds two critical updates
R -= tau * v (v^T R) and Q -= tau * (Q v) v^T.  v is masked to rows >= k
(inductive domain), tau is consumed across the whole trailing submatrix —
an ordered dependence with inductive consumption rate (paper's `tau` edge).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (eye, interpret_default, iota, take_col,
                                  take_row)


def _qr_kernel(a_ref, q_ref, r_ref, *, m: int, n: int):
    r = a_ref[0]
    rows = iota((m, 1), 0)
    q = eye(m)

    def outer(k, carry):
        q, r = carry
        # ---- householder region (non-critical: norm, sqrt, div) ----
        x = jnp.where(rows >= k, take_col(r, k), 0.0)   # masked column
        xk = take_row(x, k)
        sigma = jnp.sum(x * x, axis=0, keepdims=True)
        norm = jnp.sqrt(sigma)
        alpha = jnp.where(xk >= 0, -norm, norm)
        v = x - alpha * (rows == k).astype(r.dtype)
        vnorm2 = jnp.maximum(jnp.sum(v * v, axis=0, keepdims=True), 1e-30)
        tau = 2.0 / vnorm2
        # degenerate column: no reflection
        tau = jnp.where(norm < 1e-30, 0.0, tau)
        # ---- critical region 1: R update (v^T R then outer) ----
        w = tau * jnp.sum(v * r, axis=0, keepdims=True)  # (1, n)
        r = r - v * w
        # ---- critical region 2: Q accumulation ----
        u = tau * jnp.sum(q * v.T, axis=1, keepdims=True)  # (m, 1)
        q = q - u * v.T
        return q, r

    q, r = jax.lax.fori_loop(0, min(n, m - 1) if m > 1 else 0, outer, (q, r))
    q_ref[0] = q
    r_ref[0] = jnp.where(iota((m, n), 0) <= iota((m, n), 1), r, 0.0)


def qr_pallas(a: jax.Array, *, interpret: bool | None = None):
    """a: (B, M, N), M >= N -> (Q (B,M,M), R (B,M,N)) with a = Q @ R."""
    b, m, n = a.shape
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_qr_kernel, m=m, n=n),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((1, m, m), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m, m), a.dtype),
            jax.ShapeDtypeStruct((b, m, n), a.dtype),
        ],
        interpret=interpret,
        name="qr",
    )(a)
