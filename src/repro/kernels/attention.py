"""Causal flash attention with an inductive kv trip count — the flagship
LM-side FGOP kernel.

Causal attention's iteration domain is triangular: q block i attends to
kv blocks 0..i.  That is *exactly* the paper's RI stream (inner trip =
outer iterator + 1, stretch s_ji = +1), and the diagonal block's partial
tile is the implicit-vector-masking case (Feature 4).  On a rectangular
vector machine this costs 2x wasted work or scalar tails; here the
off-triangle blocks are predicated off with pl.when (compute skipped on
TPU) and the diagonal is lane-masked, never scalarized.

The online-softmax running (m, l, acc) carried across kv grid steps in
VMEM scratch is the ordered dependence between the "score" region
(critical, MXU) and the "rescale" region (non-critical exp/max, VPU).

GQA is folded into the BlockSpec index maps (kv head = q head * Hkv // H).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_default

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bkv: int,
                  kv_steps: int):
    iq, ikv = pl.program_id(2), pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # inductive trip count: kv blocks 0..iq active for causal
    active = (ikv <= iq) if causal else (ikv >= 0)

    @pl.when(active)
    def _compute():
        q = q_ref[0, 0]                                   # (bq, d)
        k = k_ref[0, 0]                                   # (bkv, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bkv)
        if causal:
            # implicit masking of the diagonal (partial) tile
            qi = iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 0)
            ki = ikv * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (bq, bkv)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = iq if causal else kv_steps - 1

    @pl.when(ikv == last)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, scale: float | None = None,
                           bq: int = 128, bkv: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B,H,S,D); k/v: (B,Hkv,S,D), H % Hkv == 0. Returns (B,H,S,D)."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0
    assert causal is False or sq == skv, "causal path assumes square attn"
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    kv_steps = cdiv(skv, bkv)
    if interpret is None:
        interpret = interpret_default()
    grp = h // hkv

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bkv=bkv, kv_steps=kv_steps),
        grid=(b, h, cdiv(sq, bq), kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h_, iq, ikv: (b_, h_, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, iq, ikv: (b_, h_ // grp, ikv, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, iq, ikv: (b_, h_ // grp, ikv, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, iq, ikv: (b_, h_, iq, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
