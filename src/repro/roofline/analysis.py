"""Roofline derivation from compiled dry-run artifacts.

Three terms (seconds), with the target chip's peaks from ``PEAKS``:
  compute    = HLO_FLOPs / (chips * peak bf16 FLOP/s)
  memory     = HLO_bytes / (chips * peak HBM B/s)
  collective = collective_bytes / (chips * ICI B/s per link)

cost_analysis() provides flops/bytes; collective bytes are parsed from
the *optimized* (post-SPMD) HLO text, summing result-shape bytes of each
collective op weighted by a transfer factor:
  all-reduce 2x (reduce-scatter + all-gather ring), all-gather (g-1)/g,
  reduce-scatter (g-1)/g, all-to-all (g-1)/g, collective-permute 1x.
"""
from __future__ import annotations

import dataclasses
import json
import re

import numpy as np


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bw: float         # HBM B/s per chip
    ici_bw: float         # ICI B/s per link


# Per-chip peaks keyed by jax ``Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peak table's row for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-op-type result bytes x transfer factor, from optimized HLO."""
    out: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes = m.group(1) or m.group(2)
        op = m.group(3)
        b = _shape_bytes(shapes) * _FACTOR[op]
        out[op] = out.get(op, 0.0) + b
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: dict[str, float]
    model_flops: float
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    useful_ratio: float = 0.0
    bytes_per_device: float = 0.0
    device_kind: str = V5E        # the chip the roofline is drawn for

    def finish(self):
        pk = peaks(self.device_kind)
        self.t_compute = self.hlo_flops / (self.chips * pk.flops)
        self.t_memory = self.hlo_bytes / (self.chips * pk.hbm_bw)
        self.t_collective = self.coll_bytes / (self.chips * pk.ici_bw)
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.bottleneck = max(terms, key=terms.get)
        self.useful_ratio = (self.model_flops / self.hlo_flops
                             if self.hlo_flops else 0.0)
        return self

    @property
    def step_time(self) -> float:
        """Optimistic overlap model: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline-projected step time."""
        if self.step_time == 0:
            return 0.0
        return self.model_flops / (
            self.chips * peaks(self.device_kind).flops * self.step_time)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["step_time"] = self.step_time
        d["mfu"] = self.mfu
        return d


def analyze(arch: str, shape: str, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, model_flops: float,
            bytes_per_device: float = 0.0) -> Roofline:
    coll = collective_bytes(hlo_text)
    r = Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        model_flops=model_flops,
        bytes_per_device=bytes_per_device,
    )
    return r.finish()


def _attn_flops_fwd(cfg, tokens: int, seq: int) -> float:
    """Causal self-attention matmul FLOPs (QK^T + PV), forward pass.
    Counted for full-attention stacks; hybrid counts its shared blocks;
    ssm/enc-dec kept conservative (0 / decoder-only)."""
    if cfg.family in ("dense", "moe", "vlm"):
        layers = cfg.n_layers
    elif cfg.family == "hybrid" and cfg.shared_every:
        layers = cfg.n_layers // cfg.shared_every
    elif cfg.family == "audio":
        layers = cfg.dec_layers          # decoder self-attn (causal)
    else:
        return 0.0
    # 2 matmuls x 2 flops/MAC x tokens x seq x H x Dh, causal half
    return 2.0 * 2.0 * tokens * seq * cfg.n_heads * cfg.d_head \
        * layers * 0.5


def model_flops_train(cfg, tokens: int, seq: int | None = None) -> float:
    """PaLM-style MFU numerator: 6*N_active*D + 3x fwd attention flops."""
    n = cfg.active_param_count()
    base = 6.0 * n * tokens
    if seq:
        base += 3.0 * _attn_flops_fwd(cfg, tokens, seq)
    return base


def model_flops_prefill(cfg, tokens: int, seq: int) -> float:
    """Forward-only: 2*N_active*D + fwd attention flops."""
    return 2.0 * cfg.active_param_count() * tokens \
        + _attn_flops_fwd(cfg, tokens, seq)


def model_flops_decode(cfg, batch: int, ctx: int) -> float:
    n = cfg.active_param_count()
    base = 2.0 * n * batch  # one token per sequence
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        attn = 2.0 * 2.0 * batch * cfg.n_layers * cfg.n_heads \
            * cfg.d_head * ctx
        base += attn
    return base


def load_results(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]
