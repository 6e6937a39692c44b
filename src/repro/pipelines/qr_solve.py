"""Fused least squares: Householder QR + implicit Q^T b + back
substitution in ONE Pallas grid cell (paper Fig. 6 chained with Fig. 9).

The fusion is structural, not just spatial: Q is never formed.  Each
reflector (v, tau) — the non-critical point/vector region — is applied to
the trailing columns of R *and* to the right-hand sides in the same outer
iteration (two critical MXU-shaped regions sharing one produced value:
the paper's inductive-consumption `tau` edge).  After min(m-1, n)
reflections the rhs holds Q^T b, and the back substitution on the n x n
upper triangle of R runs in the same kernel, everything VMEM-resident.

Pivot guard: a degenerate (zero-norm) column takes tau = 0 (identity
reflector) and the back substitution divides by a clamped diagonal, so
rank-deficient systems stay finite.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (dot, interpret_default, iota, put_col,
                                  put_row, resolve_backend, take_col,
                                  take_row)
from repro.kernels.qr import qr_pallas
from repro.kernels.trisolve import trisolve_pallas
from repro.pipelines.cholesky_solve import (TILED_BS, TILED_PAD_SCOPE,
                                            TILED_VMEM_BUDGET_BYTES,
                                            _row_block, pad_identity,
                                            pad_rows, tiled_padded_n)

DEFAULT_TINY = 1e-20


def householder(x, g, *, tiny: float = DEFAULT_TINY):
    """Reflector (v, tau) zeroing column ``x`` (r, 1) below row ``g``
    (the non-critical householder region: norm, sqrt, div).  A
    degenerate (zero-norm) column gets tau = 0, the identity."""
    rows = iota(x.shape, 0)
    x = jnp.where(rows >= g, x, 0.0)                  # masked column (F4)
    xk = take_row(x, g)
    norm = jnp.sqrt(jnp.sum(x * x, axis=0, keepdims=True))
    alpha = jnp.where(xk >= 0, -norm, norm)
    v = x - alpha * (rows == g).astype(x.dtype)
    vnorm2 = jnp.maximum(jnp.sum(v * v, axis=0, keepdims=True), tiny)
    tau = jnp.where(norm < tiny, 0.0, 2.0 / vnorm2)   # degenerate: skip
    return v, tau


def reflect(v, tau, mat):
    """(I - tau v v^T) mat: v^T mat as one sublane reduction, then the
    rank-1 update (a critical region)."""
    return mat - v * (tau * jnp.sum(v * mat, axis=0, keepdims=True))


def reflect_step(k, r, y, *, tiny: float = DEFAULT_TINY):
    """One fused outer iteration: build reflector k, apply it to R and,
    in the same iteration, to the right-hand sides (the fused solve)."""
    v, tau = householder(take_col(r, k), k, tiny=tiny)
    return reflect(v, tau, r), reflect(v, tau, y)


def back_substitute_r(r, y, *, n: int, tiny: float, thresh=None):
    """Back substitution on R[:n,:n] x = (Q^T b)[:n], shared by the
    unblocked, blocked, and tiled kernels.

    Uses a relative deficiency threshold from R's diagonal: a pivot
    below it marks a numerically dependent column, whose solution
    component is ZEROED (clamping the divisor instead would overflow
    float32: with R = [[0,1],[0,0]] a clamped 1/tiny cascades to inf
    through the remaining rows).  ``thresh`` overrides the local
    diagonal-derived threshold — the tiled kernel solves one (bs, bs)
    diagonal block at a time, so it passes the GLOBAL R-diagonal
    threshold accumulated during the panel sweep.
    """
    r = r[:n]
    z = y[:n]
    rows_n = iota((n, 1), 0)
    if thresh is None:
        diag = jnp.where(rows_n == iota((1, n), 1), jnp.abs(r), 0.0)
        thresh = jnp.maximum(
            1e-6 * jnp.max(diag, axis=(0, 1), keepdims=True), tiny)

    def bwd(i, z):
        k = n - 1 - i
        col = take_col(r, k)
        rkk = take_row(col, k)
        ok = jnp.abs(rkk) > thresh
        xk = jnp.where(ok, take_row(z, k) / jnp.where(ok, rkk, 1.0), 0.0)
        z = put_row(z, k, xk)
        return z - jnp.where(rows_n < k, col, 0.0) * xk

    return jax.lax.fori_loop(0, n, bwd, z)


def _qr_solve_kernel(a_ref, b_ref, x_ref, *, m: int, n: int,
                     tiny: float):
    nref = min(n, m - 1) if m > 1 else 0
    r, y = jax.lax.fori_loop(
        0, nref, lambda k, c: reflect_step(k, c[0], c[1], tiny=tiny),
        (a_ref[0], b_ref[0]))                         # (m, n), (m, k)
    x_ref[0] = back_substitute_r(r, y, n=n, tiny=tiny)


def qr_solve_pallas(a: jax.Array, b: jax.Array, *,
                    tiny: float = DEFAULT_TINY,
                    interpret: bool | None = None) -> jax.Array:
    """Least squares min ||a @ x - b||. a: (B,M,N) with M >= N,
    b: (B,M,K) -> x: (B,N,K).  One pallas_call, Q never materialized."""
    bsz, m, n = a.shape
    b2, m2, k = b.shape
    assert m == m2 and bsz == b2 and m >= n, (a.shape, b.shape)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_qr_solve_kernel, m=m, n=n, tiny=tiny),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, k), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, k), b.dtype),
        interpret=interpret,
        name="qr_solve",
    )(a, b)


def _qr_panel_reflect_step(j, carry, *, o, tiny: float):
    """Reflector ``g = o + j`` built from and applied to the panel only;
    (v, tau) accumulated for the compact-WY block apply."""
    pan, v_acc, tau_acc = carry
    v, tau = householder(take_col(pan, j), o + j, tiny=tiny)
    return (reflect(v, tau, pan), put_col(v_acc, j, v),
            put_col(tau_acc, j, tau))


def _wy_t_step(j, t, *, vt_v, taus):
    """Column ``j`` of the compact-WY ``T`` (LAPACK larft, forward
    columnwise): T[:j, j] = -tau_j * T[:j, :j] @ (V^T v_j); T[j,j] =
    tau_j.  Columns >= j of the carried ``t`` are still zero, so the
    full-width product only consumes finished columns."""
    bs = t.shape[0]
    rows = iota((bs, 1), 0)
    z = jnp.where(iota((1, bs), 1) < j, take_col(vt_v, j).T, 0.0)
    tau_j = take_col(taus, j)
    tcol = -tau_j * jnp.sum(t * z, axis=1, keepdims=True)
    tcol = jnp.where(rows < j, tcol, 0.0)
    tcol = tcol + tau_j * (rows == j).astype(t.dtype)
    return put_col(t, j, tcol)


def _panel_wy(pan, *, o, tiny: float):
    """Factor one (m, bs) panel into its reflectors and their
    compact-WY form: returns (R panel, V, T)."""
    m, bs = pan.shape
    pan, v, taus = jax.lax.fori_loop(
        0, bs, functools.partial(_qr_panel_reflect_step, o=o, tiny=tiny),
        (pan, jnp.zeros((m, bs), jnp.float32),
         jnp.zeros((1, bs), jnp.float32)))
    # T build: one V^T V gram + bs short column steps
    t = jax.lax.fori_loop(
        0, bs, functools.partial(_wy_t_step, vt_v=dot(v.T, v), taus=taus),
        jnp.zeros((bs, bs), jnp.float32))
    return pan, v, t


def _wy_apply(v, t, mat):
    """Block reflector Q_p^T mat = mat - V T^T V^T mat: the whole panel's
    reflectors as three GEMMs (critical MXU regions) instead of bs
    rank-1 updates."""
    return mat - dot(v, dot(t.T, dot(v.T, mat)))


def _qr_solve_blocked_kernel(a_ref, b_ref, x_ref, r_scr, *, n: int,
                             bs: int, tiny: float):
    steps = n // bs
    a = a_ref[0]                                      # (m, n)
    for p in range(steps):                            # column slabs
        r_scr[p] = a[:, p * bs:(p + 1) * bs]

    def panel_step(p, y):
        pan, v, t = _panel_wy(r_scr[p], o=p * bs, tiny=tiny)
        r_scr[p] = pan

        def _trail(q, carry):
            r_scr[q] = _wy_apply(v, t, r_scr[q])
            return carry

        jax.lax.fori_loop(p + 1, steps, _trail, 0)
        return _wy_apply(v, t, y)

    y = jax.lax.fori_loop(0, steps, panel_step, b_ref[0])
    r = jnp.concatenate([r_scr[p] for p in range(steps)], axis=1)
    x_ref[0] = back_substitute_r(r, y, n=n, tiny=tiny)


def qr_solve_blocked(a: jax.Array, b: jax.Array, *, bs: int | None = None,
                     tiny: float = DEFAULT_TINY,
                     interpret: bool | None = None) -> jax.Array:
    """Blocked (compact-WY) fused least squares — the large-n fast path.

    Same contract as :func:`qr_solve_pallas` but the Householder
    reflectors are accumulated per ``bs``-column panel into (V, T) and
    applied to the trailing columns and right-hand sides as rank-``bs``
    GEMMs (Q is still never formed).  Registered as the ``blocked``
    variant of the ``qr_solve`` spec; the dispatcher picks it for
    N >= 128.
    """
    bsz, m, n = a.shape
    b2, m2, k = b.shape
    assert m == m2 and bsz == b2 and m >= n, (a.shape, b.shape)
    if bs is None:
        bs = 64 if n % 64 == 0 else 32
    assert n % bs == 0 and n >= bs, (n, bs)
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_qr_solve_blocked_kernel, n=n, bs=bs,
                          tiny=tiny),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, m, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, k), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, k), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, k), b.dtype),
        scratch_shapes=[pltpu.VMEM((n // bs, m, bs), jnp.float32)],
        interpret=interpret,
        name="qr_solve_blocked",
    )(a, b)


# ---------------------------------------------------------------------------
# True sub-matrix tiling: HBM-resident trailing matrix, O(m*bs) VMEM
# ---------------------------------------------------------------------------
#
# Same data-tiling scheme as ``cholesky_solve_tiled`` (see the long
# comment there): grid = (lanes, steps + 1, tiles) with
# steps = tiles = n // bs, the (m, n) matrix HBM-resident in a
# ``pl.ANY`` work buffer, one (m, bs) column slab DMA'd per cell.
# The panel cell factors bs Householder reflectors panel-locally,
# accumulates compact-WY (V, T) in VMEM scratch, and applies the block
# reflector to the right-hand sides; trailing cells stream their slab
# through the rank-bs block apply; the final phase back-substitutes R
# right-looking over reverse-streamed slabs (each cell solves its
# (bs, bs) diagonal block against the GLOBAL deficiency threshold
# accumulated in SMEM during the panel sweep, then pushes the update to
# the rows above).

def qr_tiled_vmem_floats(m: int, n: int, bs: int, k: int) -> int:
    """Per-grid-cell VMEM working set of the tiled least squares, in
    float32 elements — slab (m, bs) + panel carry (2, m, bs) + V (m, bs)
    + T (bs, bs) + rhs carry (m, k) + b block (m, k) + x block (n, k)."""
    return 4 * m * bs + bs * bs + 2 * m * k + n * k


def _qr_solve_tiled_kernel(a_hbm, b_ref, x_ref, r_hbm, slab_scr, pan_scr,
                           v_scr, t_scr, y_scr, dmax_scr, sem, *, n: int,
                           n_job: int, bs: int, steps: int, tiny: float):
    i = pl.program_id(0)
    s = pl.program_id(1)                  # panel step; == steps: back-sub
    t = pl.program_id(2)                  # column tile

    @pl.when((s == 0) & (t == 0))
    def _init():
        y_scr[...] = b_ref[0].astype(jnp.float32)
        dmax_scr[0] = 0.0
        cp = pltpu.make_async_copy(a_hbm.at[i, :, pl.ds(0, bs)],
                                   slab_scr, sem)
        cp.start()
        cp.wait()
        pan_scr[0] = slab_scr[...]

    @pl.when((s < steps) & (t == s))
    def _panel():
        o = s * bs
        pan, v, tt = _panel_wy(pan_scr[s % 2], o=o, tiny=tiny)
        y_scr[...] = _wy_apply(v, tt, y_scr[...])     # Q_p^T on the rhs
        v_scr[...] = v
        t_scr[...] = tt
        # global |diag R| max for the back-substitution threshold, over
        # the job's own columns (padded ones have |R_jj| = 1)
        cols = o + iota((1, bs), 1)
        diag = (iota((pan.shape[0], 1), 0) == cols) & (cols < n_job)
        dmax_scr[0] = jnp.maximum(
            dmax_scr[0], jnp.max(jnp.where(diag, jnp.abs(pan), 0.0)))
        slab_scr[...] = pan
        cp = pltpu.make_async_copy(slab_scr,
                                   r_hbm.at[i, :, pl.ds(o, bs)], sem)
        cp.start()
        cp.wait()

    @pl.when((s < steps) & (t > s))
    def _trailing():
        @pl.when(s == 0)
        def _from_a():
            cp = pltpu.make_async_copy(a_hbm.at[i, :, pl.ds(t * bs, bs)],
                                       slab_scr, sem)
            cp.start()
            cp.wait()

        @pl.when(s > 0)
        def _from_r():
            cp = pltpu.make_async_copy(r_hbm.at[i, :, pl.ds(t * bs, bs)],
                                       slab_scr, sem)
            cp.start()
            cp.wait()

        slab = _wy_apply(v_scr[...], t_scr[...], slab_scr[...])
        slab_scr[...] = slab
        cp = pltpu.make_async_copy(slab_scr,
                                   r_hbm.at[i, :, pl.ds(t * bs, bs)], sem)
        cp.start()
        cp.wait()

        @pl.when(t == s + 1)              # double-buffered panel carry
        def _stash():
            pan_scr[(s + 1) % 2] = slab

    @pl.when(s == steps)
    def _backsub():
        rt = steps - 1 - t                # reverse slab order
        o = rt * bs
        cp = pltpu.make_async_copy(r_hbm.at[i, :, pl.ds(o, bs)],
                                   slab_scr, sem)
        cp.start()
        cp.wait()
        thresh = jnp.maximum(1e-6 * dmax_scr[0], tiny)
        xt = back_substitute_r(_row_block(slab_scr, rt, bs),
                               _row_block(y_scr, rt, bs), n=bs, tiny=tiny,
                               thresh=thresh)
        y_scr[pl.ds(pl.multiple_of(o, bs), bs), :] = xt
        above = jnp.where(iota((slab_scr.shape[0], 1), 0) < o,
                          slab_scr[...], 0.0)
        y_scr[...] = y_scr[...] - dot(above, xt)

        @pl.when(t == steps - 1)
        def _finish():
            x_ref[0] = y_scr[...][:n].astype(x_ref.dtype)


def qr_solve_tiled(a: jax.Array, b: jax.Array, *, bs: int | None = None,
                   tiny: float = DEFAULT_TINY,
                   interpret: bool | None = None) -> jax.Array:
    """True sub-matrix tiled fused least squares — the HBM-scale path.

    Same contract as :func:`qr_solve_pallas` (a: (B,M,N), M >= N,
    b: (B,M,K) -> x: (B,N,K)) but the matrix stays HBM-resident: per
    grid cell one (M, bs) column slab plus the compact-WY (V, T) of the
    current panel live in VMEM — ``qr_tiled_vmem_floats`` = O(M*bs).
    Registered as the ``tiled`` variant of the ``qr_solve`` spec; the
    dispatcher picks it for N >= 512.

    With ``bs`` unset the slabs are ``TILED_BS`` wide and an N they do
    not divide runs as ``[[A, 0], [0, I]]`` against ``[b; 0]``: p more
    columns and p more rows, to ``tiled_padded_n(N)`` columns (padded on
    the device under the ``tiled_pad`` scope).  The padded unknowns
    solve to 0 and the first N rows of the answer come back.
    """
    bsz, m, n = a.shape
    b2, m2, k = b.shape
    assert m == m2 and bsz == b2 and m >= n, (a.shape, b.shape)
    n_job = n
    if bs is None:
        bs, n = TILED_BS, tiled_padded_n(n)
        if n > n_job:
            with jax.named_scope(TILED_PAD_SCOPE):
                a = pad_identity(a, n - n_job)
                b = pad_rows(b, n - n_job)
            m += n - n_job
    assert n % bs == 0 and n >= 2 * bs, (n, bs)
    assert qr_tiled_vmem_floats(m, n, bs, k) * 4 <= \
        TILED_VMEM_BUDGET_BYTES, (m, n, bs, k)
    if interpret is None:
        interpret = interpret_default()
    steps = n // bs
    x, _ = pl.pallas_call(
        functools.partial(_qr_solve_tiled_kernel, n=n, n_job=n_job, bs=bs,
                          steps=steps, tiny=tiny),
        grid=(bsz, steps + 1, steps),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, m, k), lambda i, s, t: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n, k), lambda i, s, t: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n, k), b.dtype),
            jax.ShapeDtypeStruct((bsz, m, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((m, bs), jnp.float32),
            pltpu.VMEM((2, m, bs), jnp.float32),
            pltpu.VMEM((m, bs), jnp.float32),
            pltpu.VMEM((bs, bs), jnp.float32),
            pltpu.VMEM((m, k), jnp.float32),
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="qr_solve_tiled",
    )(a, b)
    if n > n_job:
        with jax.named_scope(TILED_PAD_SCOPE):
            x = x[:, :n_job]
    return x


def qr_solve_unfused(a: jax.Array, b: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """No-fusion baseline: explicit Q via qr_pallas, a GEMM for Q^T b, and
    a separate triangular-solve pallas_call (three HBM round-trips)."""
    q, r = qr_pallas(a, interpret=interpret)
    n = a.shape[-1]
    qtb = jnp.einsum("bmk,bmj->bkj", q, b)[:, :n, :]
    return trisolve_pallas(r[:, :n, :n], qtb, lower=False,
                           interpret=interpret)


def _qr_solve_xla(a: jax.Array, b: jax.Array) -> jax.Array:
    q, r = jnp.linalg.qr(a)                          # reduced: (B,M,N)
    qtb = jnp.einsum("bmn,bmk->bnk", q, b)
    return jax.vmap(partial(jax.scipy.linalg.solve_triangular,
                            lower=False))(r, qtb)


@partial(jax.jit, static_argnames=("backend",))
def qr_solve(a: jax.Array, b: jax.Array, *,
             backend: str | None = None) -> jax.Array:
    """Public wrapper with backend dispatch (pallas on TPU, xla off)."""
    if resolve_backend(backend) == "pallas":
        return qr_solve_pallas(a, b)
    return _qr_solve_xla(a, b)
