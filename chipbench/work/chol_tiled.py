"""Work of the tiled Cholesky solve, per lane, from its shapes.

Per lane with an SPD matrix A (n x n) and right-hand sides B (n x k), at
the job's own n (the rows and columns the entry point pads to whole
128-wide slabs are not the job's work):

  Cholesky factor                   n^3 / 3
  forward and back substitution     2 x n^2 k

Bytes are the least a lane must move: A and B read once and the
(n x k) answer written once, in float32.
"""
from __future__ import annotations

F32 = 4
KERNEL = "cholesky_solve_tiled"


def per_lane(shapes: tuple) -> tuple[float, float]:
    (n, _), (_, k) = shapes
    flops = n ** 3 / 3.0 + 2.0 * n * n * k
    nbytes = F32 * (n * n + 2 * n * k)
    return flops, nbytes


def match(name: str, stats: list[str]) -> bool:
    """The tiled Cholesky's own device op, by the name its
    ``pallas_call`` gives the custom call (``cholesky_solve_tiled.1
    custom-call tpu_custom_call``): not the padding ops around it, whose
    metadata may name the kernel's entry point too."""
    op = name.split(" ")[0]
    return op.split(".")[0] == KERNEL and "tpu_custom_call" in name
