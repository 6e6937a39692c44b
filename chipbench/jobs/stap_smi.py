"""Airborne STAP: full-DOF sample-matrix-inversion adaptive weights.

A request is one CPI (coherent processing interval) of an ``N``-channel,
``M``-pulse array: ``segments`` jobs, one weight solve per range segment.
The CPI's ``range_cells`` space-time snapshots (``N M`` complex each) are
clutter plus noise: ``clutter_patches`` patches spread in azimuth over
the front half-plane, each a space-time steering vector on the clutter
ridge (Doppler = ``beta`` x spatial frequency, half-wavelength element
spacing) with a complex Gaussian amplitude, the clutter-to-noise ratio
``cnr_db`` per element, and white noise of power ``noise_power``.
Segment ``s`` trains on ``training`` consecutive cells, the windows
spread evenly over the CPI and overlapping, and its job solves

    R w = s,   R = X^H X / K + delta I,

for the ``doppler_bins`` steering vectors ``s`` of one look angle
(broadside), with diagonal loading ``delta = loading``.  The program is
real only, so a job is the real embedding: ``A = [[Re R, -Im R],
[Im R, Re R]]`` (2NM x 2NM, SPD) and ``B = [Re S; Im S]`` (2NM x
doppler_bins), float32; the answer is ``[Re W; Im W]``.

``reference`` is NumPy complex128 on the complex NM system rebuilt from
the jobs' float32 inputs; ``control`` is the same solve one precision
below the kernel's: a blocked Cholesky and both substitutions with every
product between blocks in three-pass bfloat16 (``high``) and float32
within the panels.  Neither imports the program.
"""
from __future__ import annotations

import numpy as np

from chipbench.precision import dot_high

PIPELINE = "cholesky_solve"

# the control's panel width: products between panels are three-pass
# bfloat16, the work within a panel float32
CONTROL_BLOCK = 64


def dof(cfg: dict) -> int:
    """Complex degrees of freedom, channels x pulses."""
    return cfg["channels"] * cfg["pulses"]


def shapes(cfg: dict) -> tuple:
    """Per-job argument shapes, as the program receives them."""
    n = 2 * dof(cfg)
    return ((n, n), (n, cfg["doppler_bins"]))


def _space_time(cfg: dict, fs: np.ndarray, fd: np.ndarray) -> np.ndarray:
    """(N M, P) space-time steering vectors, pulse-major, of spatial
    frequencies ``fs`` and normalised Dopplers ``fd``."""
    a = np.exp(2j * np.pi * np.outer(np.arange(cfg["channels"]), fs))
    b = np.exp(2j * np.pi * np.outer(np.arange(cfg["pulses"]), fd))
    return (b[:, None, :] * a[None, :, :]).reshape(dof(cfg), -1)


def steering(cfg: dict) -> np.ndarray:
    """(N M, doppler_bins) steering vectors at broadside, one per
    Doppler bin of [-1/2, 1/2)."""
    q = cfg["doppler_bins"]
    return _space_time(cfg, np.zeros(q), np.arange(q) / q - 0.5)


def _embed(r: np.ndarray, s: np.ndarray) -> tuple:
    f32 = lambda x: np.ascontiguousarray(x, dtype=np.float32)  # noqa: E731
    a = np.block([[r.real, -r.imag], [r.imag, r.real]])
    return f32(a), f32(np.concatenate([s.real, s.imag]))


def make_request(cfg: dict, rng: np.random.Generator) -> list[tuple]:
    """One CPI's jobs, float32 ``(A, B)`` pairs, drawn in bulk from
    ``rng``."""
    n, cells, k = dof(cfg), cfg["range_cells"], cfg["training"]
    p = cfg["clutter_patches"]
    phi = ((np.arange(p) + 0.5) / p - 0.5) * np.pi
    fs = 0.5 * np.sin(phi)
    clutter = _space_time(cfg, fs, cfg["beta"] * fs)         # (n, p)
    cnr = 10.0 ** (cfg["cnr_db"] / 10.0) * cfg["noise_power"]
    amp = (rng.standard_normal((cells, p))
           + 1j * rng.standard_normal((cells, p))) * np.sqrt(cnr / p / 2.0)
    noise = (rng.standard_normal((cells, n))
             + 1j * rng.standard_normal((cells, n))) \
        * np.sqrt(cfg["noise_power"] / 2.0)
    x = amp @ clutter.T + noise                               # (cells, n)
    s = steering(cfg)
    jobs = []
    segs = cfg["segments"]
    for seg in range(segs):
        start = seg * (cells - k) // max(segs - 1, 1)
        xs = x[start:start + k]
        r = xs.conj().T @ xs / k + cfg["loading"] * np.eye(n)
        jobs.append(_embed((r + r.conj().T) / 2.0, s))
    return jobs


def reference(cfg: dict, args_list: list[tuple]) -> np.ndarray:
    """(J, 2NM, doppler_bins) float64 answers for the jobs' exact float32
    inputs: the complex system rebuilt from the embedding, solved in
    complex128."""
    n = dof(cfg)
    a = np.stack([x[0] for x in args_list]).astype(np.float64)
    b = np.stack([x[1] for x in args_list]).astype(np.float64)
    r = a[:, :n, :n] + 1j * a[:, n:, :n]
    w = np.linalg.solve(r, b[:, :n] + 1j * b[:, n:])
    return np.concatenate([w.real, w.imag], axis=-2)


def control(cfg: dict, args_list: list[tuple]) -> np.ndarray:
    """The reference in the precision below the program's: a left-looking
    blocked Cholesky of the real embedding, then blocked forward and back
    substitution, every product between blocks in three-pass bfloat16
    (``dot_high``) and the rest, within each ``CONTROL_BLOCK``-wide panel,
    float32 on the host (so that no backend's library factor decides its
    precision).  (J, 2NM, doppler_bins) float32."""
    a = np.stack([x[0] for x in args_list])
    b = np.stack([x[1] for x in args_list])
    n = a.shape[-1]
    t = lambda m: np.swapaxes(m, -1, -2)  # noqa: E731
    high = lambda x, y: np.asarray(dot_high(x, y))  # noqa: E731
    blocks = [(j, min(j + CONTROL_BLOCK, n))
              for j in range(0, n, CONTROL_BLOCK)]
    l = np.zeros_like(a)
    for j, e in blocks:
        p = a[:, j:, j:e].copy()
        if j:
            p -= high(l[:, j:, :j], t(l[:, j:e, :j]))
        for k in range(e - j):            # the panel, column by column
            p[:, k:, k] /= np.sqrt(p[:, k, k])[:, None]
            p[:, k + 1:, k + 1:] -= p[:, k + 1:, k, None] \
                * p[:, None, k + 1:e - j, k]
        l[:, j:, j:e] = p
    l = np.tril(l)
    z = np.zeros_like(b)
    for j, e in blocks:                   # L z = b
        r = b[:, j:e] - (high(l[:, j:e, :j], z[:, :j]) if j else 0.0)
        for k in range(e - j):
            r[:, k] /= l[:, j + k, j + k, None]
            r[:, k + 1:] -= l[:, j + k + 1:e, j + k, None] * r[:, None, k]
        z[:, j:e] = r
    x = np.zeros_like(b)
    for j, e in reversed(blocks):         # L^T x = z
        r = z[:, j:e] - (high(t(l[:, e:, j:e]), x[:, e:]) if e < n else 0.0)
        for k in reversed(range(e - j)):
            r[:, k] /= l[:, j + k, j + k, None]
            r[:, :k] -= l[:, j + k, j:j + k, None] * r[:, None, k]
        x[:, j:e] = r
    return x
