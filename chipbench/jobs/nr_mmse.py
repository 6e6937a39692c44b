"""NR PUSCH equalisation: one complex MMSE system per PRB per slot.

A request is one slot: ``prbs`` jobs, each the split re/im planes
``(Hr, Hi, yr, yi)`` of one PRB, with ``H`` (m x n) an i.i.d. Rayleigh
channel constant over the PRB and ``y = H s + w`` for QPSK symbols ``s``
on ``k`` resource elements and complex white noise ``w`` of variance
``sigma2``.  The served answer is ``x = (H^H H + sigma2 I)^-1 H^H y`` as
the real-stacked ``[Re x; Im x]`` (2n x k).

``reference`` is plain NumPy in complex128; ``control`` is the same
arithmetic with every product in three-pass bfloat16 (``high``), the
precision just below the float32-at-``highest`` the kernel computes in.
Neither imports the program.
"""
from __future__ import annotations

import numpy as np

from chipbench.precision import dot_high

PIPELINE = "mmse_equalize"


def dims(cfg: dict) -> tuple[int, int, int]:
    return cfg["antennas"], cfg["layers"], cfg["rhs"]


def shapes(cfg: dict) -> tuple:
    """Per-job argument shapes, as the program receives them."""
    m, n, k = dims(cfg)
    return ((m, n), (m, n), (m, k), (m, k))


def make_request(cfg: dict, rng: np.random.Generator) -> list[tuple]:
    """One slot's jobs, float32 planes, drawn in bulk from ``rng``."""
    m, n, k = dims(cfg)
    p = cfg["prbs"]
    s2 = cfg["sigma2"]
    h = (rng.standard_normal((p, m, n)) + 1j * rng.standard_normal((p, m, n))
         ) / np.sqrt(2.0)
    qpsk = (rng.choice([-1.0, 1.0], (p, n, k))
            + 1j * rng.choice([-1.0, 1.0], (p, n, k))) / np.sqrt(2.0)
    w = (rng.standard_normal((p, m, k)) + 1j * rng.standard_normal((p, m, k))
         ) * np.sqrt(s2 / 2.0)
    y = h @ qpsk + w
    f32 = lambda a: np.ascontiguousarray(a, dtype=np.float32)  # noqa: E731
    return [(f32(h[j].real), f32(h[j].imag), f32(y[j].real), f32(y[j].imag))
            for j in range(p)]


def _complex(args_list: list[tuple]):
    hr, hi, yr, yi = (np.stack([a[i] for a in args_list]).astype(np.float64)
                      for i in range(4))
    return hr + 1j * hi, yr + 1j * yi


def reference(cfg: dict, args_list: list[tuple]) -> np.ndarray:
    """(J, 2n, k) float64 answers for the jobs' exact float32 inputs."""
    h, y = _complex(args_list)
    n = h.shape[-1]
    hh = np.conj(np.swapaxes(h, -1, -2))
    g = hh @ h + cfg["sigma2"] * np.eye(n)
    x = np.linalg.solve(g, hh @ y)
    return np.concatenate([x.real, x.imag], axis=-2)


def control(cfg: dict, args_list: list[tuple]) -> np.ndarray:
    """The reference in the precision below the program's: the complex
    Gram and matched filter as real three-pass products, then a float32
    solve of the real-embedded system.  (J, 2n, k) float32."""
    import jax
    import jax.numpy as jnp
    hr, hi, yr, yi = (jnp.asarray(np.stack([a[i] for a in args_list]))
                      for i in range(4))
    n = hr.shape[-1]
    t = lambda a: jnp.swapaxes(a, -1, -2)  # noqa: E731
    gr = dot_high(t(hr), hr) + dot_high(t(hi), hi) + cfg["sigma2"] * jnp.eye(n)
    gi = dot_high(t(hr), hi) - dot_high(t(hi), hr)
    rr = dot_high(t(hr), yr) + dot_high(t(hi), yi)
    ri = dot_high(t(hr), yi) - dot_high(t(hi), yr)
    g = jnp.concatenate([jnp.concatenate([gr, -gi], -1),
                         jnp.concatenate([gi, gr], -1)], -2)
    rhs = jnp.concatenate([rr, ri], -2)
    with jax.default_matmul_precision("highest"):
        x = jnp.linalg.solve(g, rhs)
    return np.asarray(x)
