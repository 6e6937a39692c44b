"""Chunked SSD/Mamba2 scan — ordered inter-chunk dependence (FGOP F1/F2).

The SSM recurrence h_t = a_t h_{t-1} + b_t x_t^T is strictly ordered in t
(paper Property 1/2: parallel flows with ordered fine-grain deps).  The
chunked decomposition is the REVEL move: *within* a chunk everything is
parallel MXU work over a triangular (inductive!) decay matrix L_ij =
exp(la_i - la_j), j <= i; *across* chunks a small state h (N, P) is the
ordered dependence, carried in VMEM scratch across the sequential chunk
grid dimension — never touching HBM.  The cumulative-log-decay chain is
the non-critical region; the three matmuls (CB^T, M@X, B^T X) are the
critical region.

Layouts: x (B,H,S,P), a (B,H,S), b/c (B,S,N) shared across heads (G=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_default


def _ssm_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                cs: int, n: int, p: int, chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (cs, P)
    a = a_ref[0, 0].astype(jnp.float32)          # (cs,)
    bmat = b_ref[0, 0].astype(jnp.float32)       # (cs, N)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (cs, N)
    h = h_ref[...]                               # (N, P) carried state

    # ---- non-critical region: cumulative log-decay chain ----
    la = jnp.cumsum(jnp.log(jnp.maximum(a, 1e-20)))          # (cs,)

    # ---- critical region 1: pairwise gram + triangular decay ----
    g = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (cs, cs)
    ii = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    ldec = jnp.exp(la[:, None] - la[None, :])
    mmat = jnp.where(jj <= ii, g * ldec, 0.0)    # inductive-domain mask

    # ---- critical region 2: intra-chunk output ----
    y = jax.lax.dot_general(mmat, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- inter-chunk contribution (consumes the ordered dep h) ----
    y = y + jnp.exp(la)[:, None] * jax.lax.dot_general(
        cmat, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # ---- state update (produces the ordered dep for chunk ic+1) ----
    total = la[cs - 1]
    bw = bmat * jnp.exp(total - la)[:, None]     # (cs, N)
    h_new = jnp.exp(total) * h + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (N, P)
    h_ref[...] = h_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssm_scan_pallas(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                    *, chunk: int = 128, interpret: bool | None = None):
    """x: (B,H,S,P), a: (B,H,S), b/c: (B,S,N) shared or (B,H,S,N) per-head
    -> y (B,H,S,P), h (B,H,N,P)."""
    bs, h, s, p = x.shape
    n = b.shape[-1]
    if b.ndim == 3:  # shared across heads -> broadcast (kernel is 4D)
        b = jnp.broadcast_to(b[:, None], (bs, h, s, n))
        c = jnp.broadcast_to(c[:, None], (bs, h, s, n))
    chunk = min(chunk, s)
    assert s % chunk == 0
    chunks = cdiv(s, chunk)
    if interpret is None:
        interpret = interpret_default()

    y, hf = pl.pallas_call(
        functools.partial(_ssm_kernel, cs=chunk, n=n, p=p, chunks=chunks),
        grid=(bs, h, chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda b_, h_, c_: (b_, h_, c_, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, chunk),
                         lambda b_, h_, c_: (b_, h_, c_),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda b_, h_, c_: (b_, h_, c_, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda b_, h_, c_: (b_, h_, c_, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda b_, h_, c_: (b_, h_, c_, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n, p),
                         lambda b_, h_, c_: (b_, h_, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bs, h, n, p), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_scan",
    )(x, a, b, c)
    return y, hf
