"""Fused SPD solve: Cholesky factor + forward + back substitution in ONE
Pallas grid cell (paper Figs. 5/9/13 chained as a single ordered region).

The REVEL win the paper measures is not a lone factorization — it is the
*chain* factor -> forward-solve -> back-solve executed without the matrix
ever round-tripping through memory.  Here one grid cell = one lane: the
matrix and right-hand sides stay VMEM-resident across all three stages,
and the forward substitution is interleaved *inside* the factor loop — as
soon as column k of L is finished (the ordered dependence), the divide +
AXPY of the forward solve for row k consume it.  The fori_loop carry is
REVEL's inter-region FIFO.

Numerics: only the lower triangle of A is read (the inductive-domain mask,
paper Feature 4 — verified by the NaN-poisoning test), and the pivot is
guarded by ``eps`` so singular/ill-conditioned systems produce finite
output instead of NaN lanes.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cholesky import cholesky_pallas
from repro.kernels.common import (dot, eye, interpret_default, iota,
                                  put_col, put_row, resolve_backend,
                                  take_col, take_row)
from repro.kernels.trisolve import trisolve_pallas

# Relative pivot threshold (LAPACK pstrf-style): a pivot below
# eps * max(diag(A)) marks a numerically deficient direction.  Residual
# pivots of an exactly singular float32 matrix land around
# n * ulp * ||A|| ~ 1e-6 * scale, so 1e-5 cleanly separates "deficient"
# from merely ill-conditioned.
DEFAULT_EPS = 1e-5


def pivot_threshold(a, *, eps: float):
    """Scale-relative deficiency threshold from the initial diagonal,
    as a (1, 1) array."""
    diag = jnp.where(iota(a.shape, 0) == iota(a.shape, 1), a, -jnp.inf)
    return jnp.maximum(eps * jnp.max(diag, axis=(0, 1), keepdims=True),
                       1e-30)


def factor_forward_step(k, a, y, thresh):
    """One fused outer iteration: finish column k of L, then immediately
    run the forward-substitution step that consumes it.

    a: (n, n) working matrix (lower triangle -> L in place)
    y: (n, m) right-hand sides being forward-solved in place
    thresh: deficiency threshold (see pivot_threshold)

    A pivot below ``thresh`` takes the rank-deficient path: unit diagonal,
    zeroed column, zeroed solution component — the solve proceeds on the
    numerically non-deficient subspace and every lane stays finite.
    """
    n = a.shape[0]
    rows = iota((n, 1), 0)
    # ---- point region (non-critical): guarded rsqrt of the pivot ----
    colk = take_col(a, k)
    akk = take_row(colk, k)
    ok = akk > thresh
    inv = jnp.where(ok, jax.lax.rsqrt(jnp.maximum(akk, thresh)), 0.0)
    # ---- vector region: scale column k; diagonal set to the pivot ----
    col = colk * inv
    col = jnp.where(rows == k, jnp.where(ok, akk * inv, 1.0), col)
    col = jnp.where(rows >= k, col, 0.0)              # implicit mask (F4)
    # ---- matrix region (critical): masked rank-1 trailing update ----
    live = rows > k
    mask = live & (iota((1, n), 1) > k)
    a = a - jnp.where(mask, col * col.T, 0.0)
    a = put_col(a, k, jnp.where(rows >= k, col, colk))
    # ---- fused forward substitution consuming the finished column ----
    # y[k] /= l[k,k];  y[j>k] -= l[j,k] * y[k]   (divide + masked AXPY)
    yk = take_row(y, k) * inv                         # deficient: x_k = 0
    y = put_row(y, k, yk)
    y = y - jnp.where(live, col * yk, 0.0)
    return a, y


def back_substitution_step(i, lt, y, *, n: int):
    """Back-substitution outer iteration on U = L^T, k = n-1-i:
    x[k] = y[k] / l[k,k];  y[j<k] -= l[k,j] * x[k].  ``lt`` is L
    transposed once by the caller, so row k of L is read as a column."""
    k = n - 1 - i
    ucol = take_col(lt, k)                # l[k, j] valid for j <= k
    xk = take_row(y, k) / take_row(ucol, k)   # diagonal >= sqrt(eps)
    y = put_row(y, k, xk)
    return y - jnp.where(iota((n, 1), 0) < k, ucol * xk, 0.0)


def chol_solve_inline(a, y, *, eps: float):
    """Fused factor + forward + back substitution of an SPD (n, n)
    system already resident in VMEM — the shared tail of every fused
    kernel whose chain ends in a Cholesky solve.  Returns the factored
    working matrix (L in its lower triangle) and the solution."""
    n = a.shape[0]
    thresh = pivot_threshold(a, eps=eps)
    a, y = jax.lax.fori_loop(
        0, n, lambda k, c: factor_forward_step(k, c[0], c[1], thresh),
        (a, y))
    lt = a.T
    y = jax.lax.fori_loop(
        0, n, lambda i, y_: back_substitution_step(i, lt, y_, n=n), y)
    return a, y


def symmetrize_lower(a):
    """Mirror the lower triangle over the upper: the upper half is never
    read, so garbage/NaN lanes there cannot leak into the solve."""
    tril = iota(a.shape, 0) >= iota(a.shape, 1)
    return jnp.where(tril, a, a.T)


def _cholesky_solve_kernel(a_ref, b_ref, x_ref, *l_refs, eps: float):
    a, y = chol_solve_inline(symmetrize_lower(a_ref[0]), b_ref[0], eps=eps)
    x_ref[0] = y
    if l_refs:                    # factor output requested (return_l)
        tril = iota(a.shape, 0) >= iota(a.shape, 1)
        l_refs[0][0] = jnp.where(tril, a, 0.0)


def cholesky_solve_pallas(a: jax.Array, b: jax.Array, *,
                          eps: float = DEFAULT_EPS,
                          interpret: bool | None = None,
                          return_l: bool = False):
    """Solve a @ x = b for SPD a. a: (B,N,N), b: (B,N,M) -> x (B,N,M).

    One pallas_call; factor and both substitutions fused per lane.  With
    ``return_l`` also returns the Cholesky factor (it is VMEM-resident
    anyway; without the flag no factor output is declared at all, so the
    hot serving path never pays the extra HBM write).
    """
    bsz, n, n2 = a.shape
    b2, n3, m = b.shape
    assert n == n2 == n3 and bsz == b2, (a.shape, b.shape)
    if interpret is None:
        interpret = interpret_default()
    out_specs = [pl.BlockSpec((1, n, m), lambda i: (i, 0, 0),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((bsz, n, m), b.dtype)]
    if return_l:
        out_specs.append(pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((bsz, n, n), a.dtype))
    out = pl.pallas_call(
        functools.partial(_cholesky_solve_kernel, eps=eps),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, m), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="cholesky_solve",
    )(a, b)
    return (out[0], out[1]) if return_l else out[0]


def _panel_factor_forward_step(j, carry, *, o, thresh):
    """One column of the blocked panel factor, fused with the forward
    substitution row it finishes (the blocked analog of
    ``factor_forward_step``).

    carry: ``(c, *ys)`` with c an (r, bs) column slab of the working
    matrix (columns o..o+bs; the full height, or the diagonal block alone
    with o = 0) and each y an (r, m) block of right-hand sides on the
    same rows, forward-solved alike.  ``g = o + j`` is the pivot row; the
    rank-1 update is confined to the REMAINING slab columns (cols > j) —
    trailing columns outside the slab get their whole panel's
    contribution later in one SYRK.
    """
    c, *ys = carry
    n, bs = c.shape
    rows = iota((n, 1), 0)
    cols = iota((1, bs), 1)
    g = o + j
    col = take_col(c, j)
    pivot = take_row(col, g)
    ok = pivot > thresh
    inv = jnp.where(ok, jax.lax.rsqrt(jnp.maximum(pivot, thresh)), 0.0)
    newcol = col * inv
    newcol = jnp.where(rows == g, jnp.where(ok, pivot * inv, 1.0), newcol)
    newcol = jnp.where(rows >= g, newcol, 0.0)          # implicit mask (F4)
    live = rows > g
    # rank-1 update of the remaining panel columns only: w[q] is
    # newcol[o + q], the slab's own rows laid along its columns
    w = jnp.sum(jnp.where(rows == o + cols, newcol, 0.0), axis=0,
                keepdims=True)
    w = jnp.where(cols > j, w, 0.0)
    c = c - jnp.where(live, newcol * w, 0.0)
    c = put_col(c, j, newcol)
    # fused forward substitution consuming the finished column
    out = [c]
    for y in ys:
        yg = take_row(y, g) * inv
        y = put_row(y, g, yg)
        out.append(y - jnp.where(live, newcol * yg, 0.0))
    return tuple(out)


def _trailing_update(slab, pan, pt, *, o, bs: int):
    """Rank-``bs`` SYRK of factored panel ``pan`` (n, bs) onto one
    column slab: slab[r, j] -= sum_p pan[r, p] * pt[j, p] for rows r
    below the panel (rows >= o + bs), where ``pt`` is the slab's own
    (bs, bs) row block of the panel.  ``o`` may be a traced grid value."""
    pm = jnp.where(iota((pan.shape[0], 1), 0) >= o + bs, pan, 0.0)
    return slab - dot(pm, pt.T)


def _rows(t, bs: int):
    """Rows [t*bs, (t+1)*bs) at a traced ``t``, as a ref index."""
    return pl.ds(pl.multiple_of(t * bs, bs), bs)


def _row_block(ref, t, bs: int, *lead):
    """Rows [t*bs, (t+1)*bs) of the (n, c) slab ``ref[lead]`` at a traced
    ``t`` — a sublane-dynamic ref read, which Mosaic lowers (a value
    slice would not)."""
    return ref[(*lead, _rows(t, bs), slice(None))]


def _cholesky_solve_blocked_kernel(a_ref, b_ref, x_ref, a_scr, y_scr,
                                   thr_scr, *, n: int, bs: int,
                                   eps: float):
    """One tile step of the right-looking blocked factor-solve.

    grid = (lanes, n // bs): the second grid dimension is the panel step
    (``dimension_semantics`` marks it "arbitrary" — ordered), the matrix
    (as ``n // bs`` column slabs) and right-hand sides stay resident in
    VMEM scratch across steps, so nothing round-trips HBM between panel
    factor, triangular update, and trailing SYRK — the tiled-Cholesky
    chaining of Buttari et al. inside the paper's ordered-region model.
    """
    step = pl.program_id(1)
    steps = n // bs

    @pl.when(step == 0)
    def _init():
        a = symmetrize_lower(a_ref[0]).astype(jnp.float32)
        for p in range(steps):
            a_scr[p] = a[:, p * bs:(p + 1) * bs]
        y_scr[...] = b_ref[0].astype(jnp.float32)
        thr_scr[...] = pivot_threshold(a, eps=eps)

    o = step * bs
    # ---- panel factor + fused forward substitution (bs columns) ----
    c, y = jax.lax.fori_loop(
        0, bs,
        functools.partial(_panel_factor_forward_step, o=o,
                          thresh=thr_scr[...]),
        (a_scr[step], y_scr[...]))
    a_scr[step] = c
    y_scr[...] = y

    # ---- trailing SYRK (critical MXU region): one rank-bs GEMM per
    # trailing slab applies the whole panel's update ----
    def _trail(t, carry):
        a_scr[t] = _trailing_update(a_scr[t], c,
                                    _row_block(a_scr, t, bs, step),
                                    o=o, bs=bs)
        return carry

    jax.lax.fori_loop(step + 1, steps, _trail, 0)

    # ---- back substitution once the factor is complete ----
    @pl.when(step == steps - 1)
    def _finish():
        lt = jnp.concatenate([a_scr[p] for p in range(steps)], axis=1).T
        z = jax.lax.fori_loop(
            0, n, lambda i, z_: back_substitution_step(i, lt, z_, n=n), y)
        x_ref[0] = z.astype(x_ref.dtype)


def cholesky_solve_blocked(a: jax.Array, b: jax.Array, *,
                           bs: int | None = None, eps: float = DEFAULT_EPS,
                           interpret: bool | None = None) -> jax.Array:
    """Right-looking blocked fused SPD solve — the large-n fast path.

    Same contract as :func:`cholesky_solve_pallas` (a: (B,N,N) SPD,
    b: (B,N,M) -> x) but tiled: the grid's second dimension walks panel
    steps of width ``bs`` (default: 64 when N divides, else 32), each
    step factoring one panel (with the forward substitution fused in)
    and applying the trailing update as a single rank-``bs`` SYRK on the
    MXU instead of ``bs`` rank-1 vector updates.  Registered as the
    ``blocked`` variant of the ``cholesky_solve`` spec; the dispatcher
    picks it for N >= 128.
    """
    bsz, n, n2 = a.shape
    b2, n3, m = b.shape
    assert n == n2 == n3 and bsz == b2, (a.shape, b.shape)
    if bs is None:
        bs = 64 if n % 64 == 0 else 32
    assert n % bs == 0 and n >= bs, (n, bs)
    if interpret is None:
        interpret = interpret_default()
    steps = n // bs
    return pl.pallas_call(
        functools.partial(_cholesky_solve_blocked_kernel, n=n, bs=bs,
                          eps=eps),
        grid=(bsz, steps),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i, s: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, m), lambda i, s: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n, m), lambda i, s: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bsz, n, m), b.dtype),
        scratch_shapes=[
            pltpu.VMEM((steps, n, bs), jnp.float32),
            pltpu.VMEM((n, m), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cholesky_solve_blocked",
    )(a, b)


# ---------------------------------------------------------------------------
# True sub-matrix tiling: HBM-resident trailing matrix, O(n*bs) VMEM
# ---------------------------------------------------------------------------
#
# The ``blocked`` kernel above tiles the *schedule* but still holds the
# whole (n, n) matrix in one VMEM block, capping it near n = 512.  The
# ``tiled`` kernel below tiles the *data*: the matrix lives in HBM (a
# ``pl.ANY`` ref) and every grid cell DMAs exactly one (n, bs) column
# slab into VMEM scratch, so the per-cell working set is O(n*bs) and
# n = 1024/2048 fit.  Grid = (lanes, steps + 1, tiles) with
# steps = tiles = n // bs:
#
#   cell (i, s, t) with s < steps, t == s   panel cell: factor panel s
#     from the double-buffered panel carry as a blocked panel — the
#     ordered column loop (factor with the forward substitution fused)
#     runs on the (bs, bs) diagonal block alone, its right-hand sides
#     y's own row block and the identity, which comes out as the
#     block's (guarded) inverse; the rows below the block (refined
#     once) and their forward update are then GEMMs on the MXU.  The
#     factored panel is stashed for the trailing cells and DMA'd out to
#     the HBM factor buffer.
#   cell (i, s, t) with s < steps, t > s    trailing cell: DMA slab t in,
#     apply the panel's rank-bs SYRK update, DMA it back out.  The slab
#     for t == s + 1 is additionally stashed into the *other* half of the
#     panel-carry scratch — the next panel cell factors straight from
#     VMEM instead of round-tripping HBM (double-buffered panel carry).
#   cell (i, steps, t)                      back-substitution cell: slabs
#     re-streamed in REVERSE (rt = steps-1-t); the L^T solve is
#     left-looking per column slab, so each cell needs only its own slab.
#
# Cells with t < s are idle (no DMA, no compute) — the price of a
# rectangular grid over a triangular iteration space, exactly the
# paper's inductive-domain shape.

# Per-cell VMEM ceiling for the tiled kernels: stay comfortably inside
# a TPU core's ~16 MiB vector memory (double-buffered DMA slack left).
TILED_VMEM_BUDGET_BYTES = 14 * 2 ** 20


# Slab width of the tiled kernels when the caller gives none.  Mosaic
# DMAs a column slab out of a ``pl.ANY`` ref only when the slice's lane
# extent is a whole number of 128-lane tiles, so served shapes always run
# at 128 and an n that 128 does not divide is padded up to whole slabs
# (``tiled_padded_n``).  A caller passing ``bs`` (the CPU tests' 32 and
# 64) gets no padding and must tile n evenly.
TILED_BS = 128

# Named scope of the padding ops around a tiled kernel, so that a trace
# can tell them apart from the kernel.
TILED_PAD_SCOPE = "tiled_pad"


def tiled_padded_n(n: int) -> int:
    """The width a tiled kernel runs an n-column problem at when no
    ``bs`` is given: n rounded up to whole ``TILED_BS`` slabs."""
    return -(-n // TILED_BS) * TILED_BS


def pad_identity(a: jax.Array, p: int) -> jax.Array:
    """``[[a, 0], [0, I]]``: each (m, n) lane of ``a`` (B, m, n) grown by
    ``p`` rows and ``p`` columns, an identity block in the new corner.
    The padded unknowns decouple from the real ones (the Cholesky factor
    of ``diag(A, I)`` is ``diag(L, I)``; the least-squares problem splits
    in two, the identity block's alone) and solve to 0 against a zero
    right-hand side, so the first n unknowns are the unpadded solution."""
    _, m, n = a.shape
    a = jnp.pad(a, ((0, 0), (0, p), (0, p)))
    rows = jax.lax.broadcasted_iota(jnp.int32, (m + p, n + p), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m + p, n + p), 1)
    corner = (rows - m == cols - n) & (cols >= n)
    return jnp.where(corner, jnp.ones((), a.dtype), a)


def pad_rows(b: jax.Array, p: int) -> jax.Array:
    """``b`` (B, m, k) with ``p`` zero rows appended to each lane."""
    return jnp.pad(b, ((0, 0), (0, p), (0, 0)))


def tiled_vmem_floats(n: int, bs: int, m: int) -> int:
    """Per-grid-cell VMEM working set of the tiled solve, in float32
    elements — the single source of truth for the kernel's scratch and
    block declarations, asserted O(n*bs) by the test suite and enforced
    against :data:`TILED_VMEM_BUDGET_BYTES` at call time.

      slab scratch (n, bs) + double-buffered panel carry (2, n, bs)
      + the panel's diagonal-block inverse (bs, bs)
      + rhs carry (n, m) + b block (n, m) + x block (n, m)
    """
    return 3 * n * bs + bs * bs + 3 * n * m


def _tiled_factor_cell(i, s2, t, *, first_hbm, work_hbm, slab_scr,
                       pan_scr, y_scr, sem, thresh, bs: int):
    """One factor-phase grid cell (panel at t == s2, trailing at
    t > s2) of the tiled right-looking Cholesky — shared by
    ``cholesky_solve_tiled`` and the factor phase of
    ``mmse_equalize_tiled``.  ``first_hbm`` is where a slab's FIRST read
    comes from (the raw input for the Cholesky pipeline, the work buffer
    itself for MMSE, whose Gram phase already wrote it); every later
    read and every write go to ``work_hbm``.  ``pan_scr`` is the
    double-buffered panel carry, indexed by the panel step's parity.

    The panel is blocked: the ordered column loop, with its pivot guard
    and fused forward substitution, runs on the (bs, bs) diagonal block
    S11 and on y's own row block y11 only.  Carrying the identity as
    extra right-hand sides makes the loop also return Z = L11^-1 (a
    deficient column's row of Z is 0), so the rows below the block are
    L21 = S21 Z^T and their forward update y2 -= L21 (Z y11) — GEMMs on
    the MXU.  A product with an explicit inverse loses about
    cond(L11) ulps, so L21 takes one refinement step,
    L21 += (S21 - L21 L11^T) Z^T, which brings it back to the column
    loop's accuracy (a rank-deficient matrix's small pivots need it).
    The panel slab comes out as before: zero above the block, then L11,
    then L21."""
    @pl.when(t == s2)
    def _panel():
        @pl.when(s2 == 0)                 # first panel: no stash yet
        def _first():
            cp = pltpu.make_async_copy(first_hbm.at[i, :, pl.ds(0, bs)],
                                       slab_scr, sem)
            cp.start()
            cp.wait()
            pan_scr[0] = slab_scr[...]

        half = s2 % 2
        l11, y11, z = jax.lax.fori_loop(  # from the pre-updated panel slab
            0, bs,
            functools.partial(_panel_factor_forward_step, o=0,
                              thresh=thresh),
            (_row_block(pan_scr, s2, bs, half), _row_block(y_scr, s2, bs),
             eye(bs)))
        below = jnp.where(iota((slab_scr.shape[0], 1), 0) >= (s2 + 1) * bs,
                          pan_scr[half], 0.0)
        l21 = dot(below, z.T)             # zero above the block's end
        l21 = l21 + dot(below - dot(l21, l11.T), z.T)      # refinement
        y_scr[...] = y_scr[...] - dot(l21, y11)
        y_scr[_rows(s2, bs), :] = y11
        pan_scr[half] = l21               # trailing cells read this
        pan_scr[half, _rows(s2, bs), :] = l11
        slab_scr[...] = pan_scr[half]
        cp = pltpu.make_async_copy(
            slab_scr, work_hbm.at[i, :, pl.ds(s2 * bs, bs)], sem)
        cp.start()
        cp.wait()

    @pl.when(t > s2)
    def _trailing():
        @pl.when(s2 == 0)
        def _from_first():
            cp = pltpu.make_async_copy(
                first_hbm.at[i, :, pl.ds(t * bs, bs)], slab_scr, sem)
            cp.start()
            cp.wait()

        @pl.when(s2 > 0)
        def _from_work():
            cp = pltpu.make_async_copy(
                work_hbm.at[i, :, pl.ds(t * bs, bs)], slab_scr, sem)
            cp.start()
            cp.wait()

        half = s2 % 2
        slab = _trailing_update(slab_scr[...], pan_scr[half],
                                _row_block(pan_scr, t, bs, half),
                                o=s2 * bs, bs=bs)
        slab_scr[...] = slab
        cp = pltpu.make_async_copy(
            slab_scr, work_hbm.at[i, :, pl.ds(t * bs, bs)], sem)
        cp.start()
        cp.wait()

        @pl.when(t == s2 + 1)             # double-buffered panel carry
        def _stash():
            pan_scr[(s2 + 1) % 2] = slab


def _tiled_backsub_cell(i, t, *, steps: int, work_hbm, slab_scr, y_scr,
                        x_ref, sem, bs: int):
    """One back-substitution grid cell (reverse slab order) of the tiled
    L^T solve, shared by the Cholesky and MMSE tiled kernels: a
    left-looking block step on column slab ``rt`` — subtract the
    contributions of the already-solved components below, then solve
    the (bs, bs) diagonal block.  Only THIS slab is touched, an O(n*bs)
    working set; the last cell writes the solution block."""
    rt = steps - 1 - t
    cp = pltpu.make_async_copy(work_hbm.at[i, :, pl.ds(rt * bs, bs)],
                               slab_scr, sem)
    cp.start()
    cp.wait()
    o = rt * bs
    below = jnp.where(iota((slab_scr.shape[0], 1), 0) >= o + bs,
                      slab_scr[...], 0.0)
    zt = _row_block(y_scr, rt, bs) - dot(below.T, y_scr[...])
    lbt = _row_block(slab_scr, rt, bs).T
    xt = jax.lax.fori_loop(
        0, bs, lambda k, zz: back_substitution_step(k, lbt, zz, n=bs), zt)
    y_scr[_rows(rt, bs), :] = xt

    @pl.when(t == steps - 1)
    def _finish():
        x_ref[0] = y_scr[...].astype(x_ref.dtype)


def _cholesky_solve_tiled_kernel(thr_ref, a_hbm, b_ref, x_ref, l_hbm,
                                 slab_scr, pan_scr, y_scr, sem, *,
                                 bs: int, steps: int):
    i = pl.program_id(0)
    s = pl.program_id(1)                  # panel step; == steps: back-sub
    t = pl.program_id(2)                  # column tile

    @pl.when((s == 0) & (t == 0))
    def _init():
        y_scr[...] = b_ref[0].astype(jnp.float32)

    @pl.when(s < steps)
    def _factor():
        _tiled_factor_cell(i, s, t, first_hbm=a_hbm, work_hbm=l_hbm,
                           slab_scr=slab_scr, pan_scr=pan_scr,
                           y_scr=y_scr, sem=sem, thresh=thr_ref[i], bs=bs)

    @pl.when(s == steps)
    def _backsub():
        _tiled_backsub_cell(i, t, steps=steps, work_hbm=l_hbm,
                            slab_scr=slab_scr, y_scr=y_scr, x_ref=x_ref,
                            sem=sem, bs=bs)


def cholesky_solve_tiled(a: jax.Array, b: jax.Array, *,
                         bs: int | None = None, eps: float = DEFAULT_EPS,
                         interpret: bool | None = None) -> jax.Array:
    """True sub-matrix tiled fused SPD solve — the HBM-scale fast path.

    Same contract as :func:`cholesky_solve_pallas` (a: (B,N,N) SPD,
    b: (B,N,M) -> x), but the matrix never sits whole in VMEM: per grid
    cell exactly one (N, bs) column slab is DMA'd in (plus the
    double-buffered panel carry), the trailing matrix stays HBM-resident
    in a ``pl.ANY`` work buffer, and the per-cell working set is
    ``tiled_vmem_floats(n, bs, m)`` = O(N*bs).  Each panel is blocked:
    the ordered column loop runs on its (bs, bs) diagonal block alone,
    and its lower rows, the forward update below it and every trailing
    update are GEMMs on the MXU.  The deficiency threshold is
    precomputed host-side (one fused O(N) diagonal reduction) because the
    first panel cell needs it before any other slab is seen.  Registered
    as the ``tiled`` variant of the ``cholesky_solve`` spec; the
    dispatcher picks it for N >= 512.

    With ``bs`` unset the slabs are ``TILED_BS`` wide and an N they do
    not divide runs as ``[[A, 0], [0, I]]`` against ``[b; 0]`` at
    ``tiled_padded_n(N)`` (padded here, on the device, under the
    ``tiled_pad`` scope); the answer's first N rows come back.
    """
    bsz, n, n2 = a.shape
    b2, n3, m = b.shape
    assert n == n2 == n3 and bsz == b2, (a.shape, b.shape)
    n_job = n
    # the threshold of the job's own diagonal: the padded unit pivots
    # must not move it
    diag = jnp.diagonal(a, axis1=-2, axis2=-1)
    thr = jnp.maximum(eps * jnp.max(diag, axis=-1), 1e-30)
    thr = thr.astype(jnp.float32)
    if bs is None:
        bs, n = TILED_BS, tiled_padded_n(n)
        if n > n_job:
            with jax.named_scope(TILED_PAD_SCOPE):
                a = pad_identity(a, n - n_job)
                b = pad_rows(b, n - n_job)
    assert n % bs == 0 and n >= 2 * bs, (n, bs)
    assert tiled_vmem_floats(n, bs, m) * 4 <= TILED_VMEM_BUDGET_BYTES, \
        (n, bs, m)
    if interpret is None:
        interpret = interpret_default()
    x, _ = _tiled_solve_and_factor(thr, a, b, bs=bs, interpret=interpret)
    if n > n_job:
        with jax.named_scope(TILED_PAD_SCOPE):
            x = x[:, :n_job]
    return x


def _tiled_solve_and_factor(thr, a, b, *, bs: int, interpret: bool):
    """The tiled kernel on whole ``bs``-wide slabs: ``(x, L)``, with L
    the factor it leaves in its HBM work buffer (zero above the
    diagonal), which ``cholesky_solve_tiled`` drops."""
    bsz, n, _ = a.shape
    m = b.shape[-1]
    steps = n // bs
    return pl.pallas_call(
        functools.partial(_cholesky_solve_tiled_kernel, bs=bs, steps=steps),
        grid=(bsz, steps + 1, steps),
        in_specs=[
            # every lane's threshold, whole in SMEM, read at program_id(0)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, n, m), lambda i, s, t: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n, m), lambda i, s, t: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, n, m), b.dtype),
            jax.ShapeDtypeStruct((bsz, n, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, bs), jnp.float32),
            pltpu.VMEM((2, n, bs), jnp.float32),
            pltpu.VMEM((n, m), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="cholesky_solve_tiled",
    )(thr, a, b)


def cholesky_solve_unfused(a: jax.Array, b: jax.Array, *,
                           interpret: bool | None = None) -> jax.Array:
    """The no-fusion baseline: factor-then-solve via THREE separate
    pallas_calls — the matrix round-trips through HBM between regions.
    Same math; this is what bench_pipelines compares against."""
    l = cholesky_pallas(a, interpret=interpret)
    z = trisolve_pallas(l, b, lower=True, interpret=interpret)
    return trisolve_pallas(jnp.swapaxes(l, -1, -2), z, lower=False,
                           interpret=interpret)


def _cholesky_solve_xla(a: jax.Array, b: jax.Array) -> jax.Array:
    """Fused-at-XLA-level fallback (one jit program, library factor)."""
    l = jnp.linalg.cholesky(a)
    z = jax.vmap(partial(jax.scipy.linalg.solve_triangular, lower=True)
                 )(l, b)
    return jax.vmap(partial(jax.scipy.linalg.solve_triangular, lower=False)
                    )(jnp.swapaxes(l, -1, -2), z)


@partial(jax.jit, static_argnames=("backend",))
def cholesky_solve(a: jax.Array, b: jax.Array, *,
                   backend: str | None = None) -> jax.Array:
    """Public wrapper with backend dispatch (pallas on TPU, xla off)."""
    if resolve_backend(backend) == "pallas":
        return cholesky_solve_pallas(a, b)
    return _cholesky_solve_xla(a, b)
