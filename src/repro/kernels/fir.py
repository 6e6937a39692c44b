"""Centro-symmetric FIR (paper's Centro-FIR workload).

Exploits h[j] == h[m-1-j]: each tap pair shares one multiply,
y[i] = sum_{j<m/2} h[j]*(x[i+j] + x[i+m-1-j]) (+ middle tap if m odd),
halving multiplies exactly as the paper's ASIC model assumes.  The signal
stays VMEM-resident (DSP-sized inputs); the grid tiles the output and each
tile slices its overlapping input window with pl.ds — overlapping windows
cannot be expressed as BlockSpec strides, so the window read is the
kernel's own (rectangular) stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_default


def _fir_kernel(x_ref, h_ref, o_ref, *, bo: int, m: int):
    i = pl.program_id(0)
    x = x_ref[0, pl.ds(i * bo, bo + m - 1)]   # overlapping window
    h = h_ref[...]                            # (m,)
    half = m // 2
    acc = jnp.zeros((bo,), jnp.float32)

    def tap(j, acc):
        # paired taps: one multiply for two symmetric positions
        lo = jax.lax.dynamic_slice(x, (j,), (bo,))
        hi = jax.lax.dynamic_slice(x, (m - 1 - j,), (bo,))
        return acc + h[j] * (lo + hi)

    acc = jax.lax.fori_loop(0, half, tap, acc)
    if m % 2 == 1:
        acc = acc + h[half] * jax.lax.dynamic_slice(x, (half,), (bo,))
    o_ref[0] = acc.astype(o_ref.dtype)


def fir_pallas(x: jax.Array, h: jax.Array, *, bo: int = 256,
               interpret: bool | None = None) -> jax.Array:
    """Valid-mode centro-symmetric FIR. x: (N,), h: (M,) symmetric.
    Returns y: (N - M + 1,). Requires (N - M + 1) % bo == 0 after the
    ops.py wrapper pads (bo is clamped for short signals)."""
    n, = x.shape
    m, = h.shape
    out = n - m + 1
    bo = min(bo, out)
    assert out % bo == 0, "ops.py must pad output length to a bo multiple"
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_fir_kernel, bo=bo, m=m),
        grid=(cdiv(out, bo),),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bo), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, out), x.dtype),
        interpret=interpret,
        name="fir",
    )(x[None, :], h)[0]
