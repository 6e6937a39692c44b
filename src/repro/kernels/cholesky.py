"""Batched Cholesky — the paper's running FGOP example (Fig. 5/13).

One Pallas grid cell = one REVEL "lane": a whole small matrix resident in
VMEM.  Inside, the three regions are fused per outer iteration k:

  point  region (non-critical): rsqrt(a[k,k])            — VPU scalar
  vector region               : scale column k            — VPU, masked
  matrix region (critical)    : rank-1 trailing update    — MXU-shaped,
                                 triangular (inductive) domain, masked

The ordered dependences point->vector->matrix and matrix->point(next k)
never leave VMEM — the carry of the fori_loop is REVEL's FIFO.  The
trailing update's iteration domain shrinks with k: an RI stream, realized
as implicit masks (paper Feature 4) instead of scalar leftovers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (interpret_default, iota, put_col,
                                  take_col, take_row)


def _cholesky_kernel(a_ref, l_ref, *, n: int):
    a = a_ref[0]
    rows = iota((n, 1), 0)

    def outer(k, a):
        # ---- point region (non-critical: rsqrt) ----
        colk = take_col(a, k)
        inv = jax.lax.rsqrt(take_row(colk, k))
        # ---- vector region: scale column k below the diagonal ----
        col = jnp.where(rows >= k, colk * inv, 0.0)  # implicit mask (F4)
        # ---- matrix region (critical): masked rank-1 update ----
        # inductive domain: rows>k & cols>k — the RI stream's mask
        live = rows > k
        mask = live & (iota((1, n), 1) > k)
        a = a - jnp.where(mask, col * col.T, 0.0)
        # write the finished L column back (ordered dep to next k)
        return put_col(a, k, jnp.where(rows >= k, col, colk))

    a = jax.lax.fori_loop(0, n, outer, a)
    l_ref[0] = jnp.where(rows >= iota((1, n), 1), a, 0.0)


def cholesky_pallas(a: jax.Array, *, interpret: bool | None = None
                    ) -> jax.Array:
    """a: (B, N, N) SPD -> L lower-triangular with a = L @ L.T."""
    b, n, n2 = a.shape
    assert n == n2, "square matrices required"
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_cholesky_kernel, n=n),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n, n), a.dtype),
        interpret=interpret,
        name="cholesky",
    )(a)
