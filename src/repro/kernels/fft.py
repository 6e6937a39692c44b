"""Batched radix-2 FFT (paper's FFT workload; RR streams per Table 5).

Iterative Cooley-Tukey, fully VMEM-resident.  The bit-reversal
permutation and the twiddle factors are host-precomputed *stream tables*
(the REVEL analog: the control core issues one stream command per stage;
the pattern state machines do the rest).  Complex values travel as
separate re/im planes (TPU has no native complex).  The stage loop is an
ordered dependence chain — stage s+1 consumes everything stage s
produced — so it stays inside one kernel rather than round-tripping HBM
per stage.

The twiddle table is CHUNKED: stage ``s`` only has ``2**s`` distinct
twiddles (w_span^off for off < span/2), so ``fft_tables`` packs stage
``s`` at offset ``2**s - 1`` for a total of ``n - 1`` complex entries.
The TPU compiler lowers no in-kernel gather, so ``stage_twiddles``
expands it host-side into one row per stage (``stages x n`` floats,
40 KiB per plane at n = 1024), and the kernel finds each butterfly
partner by a static lane rotation of the signal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default


def fft_tables(n: int):
    """Host-side stream tables: bit-reversal permutation and the CHUNKED
    twiddle table (re, im) — stage ``s`` occupies slots
    ``[2**s - 1, 2**(s+1) - 1)``, ``n - 1`` entries total."""
    stages = int(np.log2(n))
    assert 2 ** stages == n, "n must be a power of two"
    rev = np.zeros(n, np.int32)
    bits = stages
    for i in range(n):
        rev[i] = int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
    w_re = np.zeros(max(n - 1, 1), np.float32)
    w_im = np.zeros(max(n - 1, 1), np.float32)
    for s in range(stages):
        half = 1 << s
        span = half << 1
        base = half - 1                  # sum_{t<s} 2**t
        for off in range(half):
            ang = -2.0 * np.pi * off / span
            w_re[base + off] = np.cos(ang)
            w_im[base + off] = np.sin(ang)
    return rev, w_re, w_im


def stage_twiddles(n: int):
    """Per-lane twiddle rows (stages, n), re and im: row ``s`` holds, at
    lane ``i``, the stage-``s`` twiddle of ``i``'s butterfly, expanded
    host-side from the chunked table so the kernel reads it with no
    gather (the TPU compiler lowers no in-kernel gather)."""
    stages = int(np.log2(n))
    _, w_re, w_im = fft_tables(n)
    lanes = np.arange(n)
    idx = np.stack([(1 << s) - 1 + (lanes & ((1 << s) - 1))
                    for s in range(stages)])
    return w_re[idx], w_im[idx]


def _fft_kernel(xr_ref, xi_ref, wr_ref, wi_ref, or_ref, oi_ref, *, n: int,
                stages: int):
    xr = xr_ref[0]                        # (1, n), bit-reversed order
    xi = xi_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    # The stage loop is unrolled: each stage's butterfly span is then a
    # static lane rotation.  Lane i pairs with i + half when bit s of i
    # is clear (the butterfly's top u) and with i - half when it is set
    # (its bottom v); both ends share the twiddle of row s.
    for s in range(stages):
        half = 1 << s
        bottom = jnp.bitwise_and(lane, half) != 0
        wr = wr_ref[s:s + 1, :]
        wi = wi_ref[s:s + 1, :]
        ur = jnp.where(bottom, jnp.roll(xr, half, axis=1), xr)
        ui = jnp.where(bottom, jnp.roll(xi, half, axis=1), xi)
        vr = jnp.where(bottom, xr, jnp.roll(xr, n - half, axis=1))
        vi = jnp.where(bottom, xi, jnp.roll(xi, n - half, axis=1))
        # twiddle multiply (critical vector region)
        tr = wr * vr - wi * vi
        ti = wr * vi + wi * vr
        xr = jnp.where(bottom, ur - tr, ur + tr)
        xi = jnp.where(bottom, ui - ti, ui + ti)
    or_ref[0] = xr
    oi_ref[0] = xi


def fft_pallas(x_re: jax.Array, x_im: jax.Array, *,
               interpret: bool | None = None):
    """(B, N) re/im -> (re, im) of the DFT.  VMEM per lane is O(N log N)
    (signal + per-stage twiddle rows), so the paper's 1024-point size
    stays resident.  The bit-reversal permutation is applied to the
    input by XLA on the way in."""
    b, n = x_re.shape
    stages = int(np.log2(n))
    rev, _, _ = fft_tables(n)
    wr, wi = stage_twiddles(n)
    if interpret is None:
        interpret = interpret_default()
    # unit middle axis: a (1, n) block of a (B, n) array breaks the
    # TPU's (8, 128) block rule, a (1, 1, n) block of (B, 1, n) does not
    planes = lambda x: jnp.take(x, jnp.asarray(rev), axis=1)[:, None, :]
    row = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    tab = pl.BlockSpec((stages, n), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    fr, fi = pl.pallas_call(
        functools.partial(_fft_kernel, n=n, stages=stages),
        grid=(b,),
        in_specs=[row, row, tab, tab],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, n), x_re.dtype),
            jax.ShapeDtypeStruct((b, 1, n), x_im.dtype),
        ],
        interpret=interpret,
        name="fft",
    )(planes(x_re), planes(x_im), jnp.asarray(wr), jnp.asarray(wi))
    return fr[:, 0], fi[:, 0]
