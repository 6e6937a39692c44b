"""Logical-axis sharding rules (MaxText-style, hand-rolled).

Model code annotates activations/params with *logical* axis names; a rule
table maps them to mesh axes.  Rules are resolved against a concrete mesh's
axis names so the same model code runs on (data, model), on
(pod, data, model), or on a single CPU device (no rules -> no constraint).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the repo's default of no varying-manual-axes
    check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

# logical axis -> tuple of candidate mesh axes (first present ones used)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),      # DP over pods, then data axis
    "seq": (),
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_cap": ("data",),
    "fsdp": ("data",),             # weight shard dim (ZeRO-3)
    "model": ("model",),
    "data": ("data",),
    "pod": ("pod",),
    "stage": (),                   # reserved for PP experiments
    "kv_seq": ("model",),          # long-context decode: shard the cache
    "seq_sp": ("model",),          # sequence-parallel attention chunks
    "layers": (),
}

# rule overrides for serving: no FSDP gather per layer (TP-only weights)
SERVE_RULES: dict[str, tuple[str, ...]] = {
    "fsdp": (),
    "batch": ("pod", "data"),
}

_state = threading.local()


def _current():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def axis_rules(mesh, rules: dict[str, tuple[str, ...]] | None = None):
    """Activate logical->mesh resolution for `mesh` (None deactivates)."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    names = set(mesh.axis_names) if mesh is not None else set()
    prev = _current()
    _state.ctx = (rules, names, mesh)
    try:
        yield
    finally:
        _state.ctx = prev


def resolve(*logical: str | None) -> P:
    """Build a PartitionSpec from logical axis names under current rules."""
    ctx = _current()
    if ctx is None:
        return P()
    rules, names, _mesh = ctx
    out = []
    for ax in logical:
        if ax is None:
            out.append(None)
            continue
        cand = tuple(a for a in rules.get(ax, ()) if a in names)
        if not cand:
            out.append(None)
        elif len(cand) == 1:
            out.append(cand[0])
        else:
            out.append(cand)
    return P(*out)


def resolve_spec(spec: P) -> P:
    """Resolve a PartitionSpec whose entries are *logical* names."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            flat = []
            for e in entry:
                r = resolve(e)[0] if len(resolve(e)) else None
                if isinstance(r, tuple):
                    flat.extend(r)
                elif r is not None:
                    flat.append(r)
            out.append(tuple(flat) if flat else None)
        else:
            r = resolve(entry)
            r0 = r[0] if len(r) else None
            out.append(r0)
    return P(*out)


def named(spec_logical: P):
    """NamedSharding on the context mesh from a logical spec."""
    ctx = _current()
    if ctx is None:
        raise RuntimeError("axis_rules context required")
    _, _, mesh = ctx
    return jax.sharding.NamedSharding(mesh, resolve_spec(spec_logical))


def named_safe(spec_logical: P, shape: tuple[int, ...]):
    """Like named(), but drops mesh axes that don't divide the dim."""
    ctx = _current()
    if ctx is None:
        raise RuntimeError("axis_rules context required")
    _, _, mesh = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec = resolve_spec(spec_logical)
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            if shape[i] % (prod * sizes[a]) == 0:
                kept.append(a)
                prod *= sizes[a]
        out.append(tuple(kept) if len(kept) > 1
                   else (kept[0] if kept else None))
    return jax.sharding.NamedSharding(mesh, P(*out))


def constrain(x, *logical: str | None):
    """with_sharding_constraint under the active rules (identity if none)."""
    ctx = _current()
    if ctx is None:
        return x
    _, _, mesh = ctx
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, resolve(*logical)))


def current_mesh():
    """Mesh of the active axis_rules context (None outside)."""
    ctx = _current()
    return ctx[2] if ctx is not None else None


def mesh_axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def param_spec(path: tuple[str, ...], shape: tuple[int, ...]) -> P:
    """Sharding spec for a parameter, keyed by its pytree path.

    Policy (FSDP+TP, pod-replicated):
      - stacked layer dim (leading L) unsharded
      - attention/mlp weights: (fsdp, model) on the (in, out) dims
      - second projections (wo/w_down/w_out): (model, fsdp)
      - embeddings / lm head: vocab on model, embed on fsdp
      - MoE expert weights: experts on model, d_model on fsdp
      - 1-D scales/biases replicated
    """
    name = path[-1]
    stacked = "layers" in "/".join(path[:-1]) or name.startswith("stk_")
    lead: list[str | None] = [None] if stacked and len(shape) >= 2 else []

    def pads(spec):
        out = lead + list(spec)
        out += [None] * (len(shape) - len(out))
        return P(*out[: len(shape)])

    if len(shape) - len(lead) <= 1:
        return pads([None])
    if name in ("embed", "lm_head"):
        return pads(["vocab", "fsdp"]) if name == "embed" \
            else pads(["fsdp", "vocab"])
    if name in ("wi", "wg") and len(shape) - len(lead) == 3:   # MoE (E,D,F)
        return pads(["experts", "fsdp", None])
    if name == "wo" and len(shape) - len(lead) == 3:           # MoE (E,F,D)
        return pads(["experts", None, "fsdp"])
    if name in ("wq", "wk", "wv", "wi", "wg", "w_in", "w_up", "w_gates",
                "r_gates", "router", "wz"):
        return pads(["fsdp", "model"])
    if name in ("wo", "w_out", "w_down"):
        return pads(["model", "fsdp"])
    return pads([None])
