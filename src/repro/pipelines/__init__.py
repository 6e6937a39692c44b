"""Fused solver pipelines — composed FGOP workloads as single kernels.

The paper's REVEL results (Figs. 13-19) are per-kernel, but its wireless
motivation (§1, Fig. 4) is a *chain*: in a 5G MMSE receiver every
subcarrier runs channel-Gram GEMM -> Cholesky -> forward solve -> back
solve -> combine, thousands of times per slot.  Fine-grain ordered
parallelism is exactly what lets those stages overlap without spilling
the (12..32-antenna sized) matrices to memory between them.  This package
provides those chains as first-class single-``pallas_call`` kernels, one
lane (grid cell) per subcarrier/problem:

  cholesky_solve  — factor + both substitutions fused (the chain of paper
                    Fig. 5 [Cholesky regions] and Fig. 9 [Solver's
                    inductive a/b edge]); forward substitution interleaved
                    into the factor loop at column granularity.
  qr_solve        — Householder least squares (paper Fig. 6 left) with
                    Q^T b applied reflector-by-reflector (never forming
                    Q) + fused back substitution — the `tau` ordered edge
                    consumed by two critical regions per iteration.
  mmse_equalize   — the full 5G use case: H^T H + sigma^2 I (GEMM,
                    Fig. 7), fused Cholesky solve, matched-filter GEMM;
                    x = (H^H H + s I)^{-1} H^H y per subcarrier.

Each pipeline ships three faces (mirroring repro.kernels): the fused
Pallas kernel (``*_pallas``), an unfused multi-``pallas_call`` baseline
(``*_unfused`` / ``*_composed``) whose HBM round-trips the fusion
saves, and a jit'd dispatching wrapper.  All are registered in the
kernel registry (``repro.kernels.get/names/specs``) next to the
primitive kernels, so tests, the benchmark and the serving mux
enumerate them uniformly.

Each pipeline additionally registers performance *variants* the registry
dispatcher (``KernelSpec.dispatch``) selects by shape/arity: blocked
(schedule-tiled, whole matrix VMEM-resident) ``cholesky_solve_blocked``
/ ``qr_solve_blocked`` for the 128 <= n < 512 midrange, true
sub-matrix-tiled ``cholesky_solve_tiled`` / ``qr_solve_tiled`` /
``mmse_equalize_tiled`` (HBM-resident matrix, O(n*bs) VMEM slabs, DMA'd
per grid cell, n padded up to whole 128-wide slabs) for n >= 512, and the
split re/im ``mmse_equalize_split`` fast path for jobs arriving as 4
complex planes.
"""
from repro.pipelines.cholesky_solve import (cholesky_solve,  # noqa: F401
                                            cholesky_solve_blocked,
                                            cholesky_solve_pallas,
                                            cholesky_solve_tiled,
                                            cholesky_solve_unfused,
                                            tiled_padded_n,
                                            tiled_vmem_floats)
from repro.pipelines.mmse import (expand_complex_channel,  # noqa: F401
                                  mmse_equalize, mmse_equalize_blocked,
                                  mmse_equalize_composed,
                                  mmse_equalize_pallas,
                                  mmse_equalize_split,
                                  mmse_equalize_split_pallas,
                                  mmse_equalize_tiled,
                                  mmse_tiled_vmem_floats)
from repro.pipelines.pusch import (channel_estimate_pallas,  # noqa: F401
                                   pusch_chain_pallas, pusch_fft_pallas,
                                   svd_apply_pallas, svd_factor_pallas)
from repro.pipelines.qr_solve import (qr_solve,  # noqa: F401
                                      qr_solve_blocked, qr_solve_pallas,
                                      qr_solve_tiled, qr_solve_unfused,
                                      qr_tiled_vmem_floats)

__all__ = [
    "cholesky_solve", "cholesky_solve_pallas", "cholesky_solve_unfused",
    "cholesky_solve_blocked", "cholesky_solve_tiled",
    "qr_solve", "qr_solve_pallas", "qr_solve_unfused", "qr_solve_blocked",
    "qr_solve_tiled",
    "mmse_equalize", "mmse_equalize_pallas", "mmse_equalize_composed",
    "mmse_equalize_split", "mmse_equalize_split_pallas",
    "mmse_equalize_tiled", "mmse_equalize_blocked",
    "expand_complex_channel",
    "channel_estimate_pallas", "pusch_chain_pallas", "pusch_fft_pallas",
    "svd_apply_pallas", "svd_factor_pallas",
    "tiled_vmem_floats", "qr_tiled_vmem_floats", "mmse_tiled_vmem_floats",
    "tiled_padded_n",
]
