"""Work of the split re/im MMSE kernel, per lane, from its shapes.

Per lane with planes Hr, Hi (m x n) and yr, yi (m x k):

  stacked Gram   [Hr; Hi]^T [Hr; Hi]            2 (2m) n^2
  cross Gram     Hr^T Hi                        2 m n^2
  matched filter [Hr; Hi]^T [yr; yi] and ...    2 x 2 (2m) n k
  Cholesky of the real-embedded 2n system       (2n)^3 / 3
  forward and back substitution                 2 x (2n)^2 k

Bytes are the least a lane must move: the four input planes read once
and the (2n x k) answer written once, in float32.
"""
from __future__ import annotations

F32 = 4


def per_lane(shapes: tuple) -> tuple[float, float]:
    (m, n), _, (_, k), _ = shapes
    flops = (4.0 * m * n * n + 2.0 * m * n * n + 8.0 * m * n * k
             + (2 * n) ** 3 / 3.0 + 2.0 * (2 * n) ** 2 * k)
    nbytes = F32 * (2 * m * n + 2 * m * k + 2 * n * k)
    return flops, nbytes


def match(name: str, stats: list[str]) -> bool:
    """The served Pallas kernel's events: the only TPU custom call of an
    MMSE launch (the copies around it are XLA ops of their own)."""
    return any("tpu_custom_call" in s or "mmse_split" in s
               for s in [name, *stats])
