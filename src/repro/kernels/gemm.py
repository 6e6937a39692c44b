"""Blocked GEMM — the paper's non-FGOP baseline workload (RR streams).

Classic MXU-tiled matmul: grid (M/bm, N/bn, K/bk) with the K dimension
sequential ("arbitrary"), accumulating in an f32 VMEM scratch.  Block
shapes default to MXU-aligned 128s (criticality: this entire kernel is a
critical dataflow, so it owns full MXU tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_default


def _gemm_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_pallas(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """x: (M, K) @ y: (K, N) -> (M, N). Dims must divide by block sizes
    (ops.py pads); accumulation in f32."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    k_steps = cdiv(k, bk)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=(cdiv(m, bm), cdiv(n, bn), k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="gemm",
    )(x, y)
