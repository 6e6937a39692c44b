"""Batched triangular solve (paper Fig. 2 / Fig. 9 — the Solver kernel).

Forward substitution L y = b with multiple right-hand sides.  The divide
dataflow (non-critical, 1 per row) feeds the vectorized AXPY update
(critical) — production:consumption rate n-1-k:1, an inductive ordered
dependence (paper Fig. 9's a/b edge).  The trailing update is masked to
rows > k: the RI stream realized as implicit predication.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (interpret_default, iota, put_row,
                                  take_col, take_row)


def _trisolve_kernel(l_ref, b_ref, y_ref, *, n: int, lower: bool):
    l = l_ref[0]
    y = b_ref[0]                       # (n, m) rhs, solved in place
    rows = iota((n, 1), 0)

    def outer(i, y):
        k = i if lower else n - 1 - i
        lcol = take_col(l, k)
        # point region: reciprocal of the pivot (non-critical)
        inv = 1.0 / take_row(lcol, k)
        yk = take_row(y, k) * inv      # (1, m) — the produced value
        y = put_row(y, k, yk)
        # critical region: masked AXPY over the remaining rows
        live = (rows > k) if lower else (rows < k)
        return y - jnp.where(live, lcol * yk, 0.0)

    y = jax.lax.fori_loop(0, n, outer, y)
    y_ref[0] = y


def trisolve_pallas(l: jax.Array, b: jax.Array, *, lower: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """l: (B, N, N) triangular, b: (B, N, M) -> y with l @ y = b."""
    bsz, n, _ = l.shape
    _, n2, m = b.shape
    assert n == n2
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_trisolve_kernel, n=n, lower=lower),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, m), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, m), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, m), b.dtype),
        interpret=interpret,
        name="trisolve",
    )(l, b)
