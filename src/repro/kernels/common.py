"""Shared kernel plumbing: backend selection, interpret-mode default.

Every kernel in this package has three faces:
  <name>.py  — the Pallas TPU kernel (pl.pallas_call + BlockSpec)
  ops.py     — the jit'd public wrapper, backend-dispatching
  ref.py     — the pure-jnp oracle

On TPU the Pallas path compiles natively; on this CPU container it runs in
interpret=True mode (Python evaluation of the kernel body) for correctness
validation, while `backend='xla'` gives the fast pure-jnp path used as the
production fallback.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

__all__ = ["interpret_default", "on_tpu", "resolve_backend", "cdiv",
           "round_up", "sample_spd", "iota", "eye", "take_col", "take_row",
           "put_col", "put_row", "dot"]


def sample_spd(rng, b: int, n: int):
    """Batched well-conditioned SPD test matrices (B,N,N) float32 — the
    shared generator for registry cases, benchmarks, and tests."""
    import numpy as np
    a = rng.standard_normal((b, n, n)).astype(np.float32)
    return a @ a.swapaxes(-1, -2) + n * np.eye(n, dtype=np.float32)


@functools.cache
def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  A backend that fails to
    initialise raises here rather than reading as "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode exactly when the backend is not a TPU.

    ``REPRO_PALLAS_INTERPRET`` may choose the mode off a TPU; asking for
    interpret mode on a TPU is an error, because it would silently run
    every kernel in the Python interpreter instead of on the chip.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    forced = env is not None and env not in ("0", "false", "False")
    if on_tpu():
        if forced:
            raise RuntimeError(
                "REPRO_PALLAS_INTERPRET asks for interpret mode on a TPU "
                "backend; unset it to run the kernels natively")
        return False
    return forced or env is None


# ---------------------------------------------------------------------------
# In-kernel helpers the TPU compiler (Mosaic) can lower.  Mosaic cannot
# slice a VALUE at a traced offset (``a[k, k]``, ``a[:, k]``,
# ``.at[:, k].set``), which is exactly what the fine-grain step functions
# do at their loop-carried index.  These read and write one row or column
# as a masked reduction / select over 2-D iotas instead.  A read sums one
# selected term and zeros, so it returns the element bit for bit.
# ---------------------------------------------------------------------------

def iota(shape, dim: int):
    """int32 index along ``dim`` (TPU iotas must be at least 2-D)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def eye(n: int):
    """float32 identity built from iotas (no constant array in VMEM)."""
    return (iota((n, n), 0) == iota((n, n), 1)).astype(jnp.float32)


def take_col(a, k):
    """``a[:, k:k+1]`` for a traced ``k``: shape (r, 1)."""
    return jnp.sum(jnp.where(iota(a.shape, 1) == k, a, 0.0), axis=1,
                   keepdims=True)


def take_row(a, k):
    """``a[k:k+1, :]`` for a traced ``k``: shape (1, c)."""
    return jnp.sum(jnp.where(iota(a.shape, 0) == k, a, 0.0), axis=0,
                   keepdims=True)


def put_col(a, k, col):
    """``a.at[:, k].set(col)`` for a traced ``k``; ``col`` is (r, 1)."""
    return jnp.where(iota(a.shape, 1) == k, col, a)


def put_row(a, k, row):
    """``a.at[k, :].set(row)`` for a traced ``k``; ``row`` is (1, c)."""
    return jnp.where(iota(a.shape, 0) == k, row, a)


def dot(a, b):
    """In-kernel float32 matmul at full precision.  Without ``HIGHEST``
    the TPU may feed the MXU bfloat16-rounded operands, whose ~3
    significant digits the solvers' float32 tolerances do not allow."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def resolve_backend(backend: str | None) -> str:
    """'pallas' | 'xla' | None(auto: pallas on TPU, xla elsewhere)."""
    if backend is None:
        return "pallas" if on_tpu() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"backend must be 'pallas'|'xla', got {backend!r}")
    return backend


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
