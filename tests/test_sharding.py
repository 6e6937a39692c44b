"""Logical-axis sharding rules + mesh construction (distribution layer)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh, make_production_mesh


def mesh1d():
    return jax.make_mesh((1,), ("data",))


def test_resolve_without_context_is_noop():
    assert shd.resolve("batch", "seq") == P()
    x = jnp.ones((2, 2))
    assert shd.constrain(x, "batch", None) is x


def test_resolve_with_mesh_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with shd.axis_rules(mesh):
        assert shd.resolve("batch", "seq", "embed") == P("data", None, None)
        assert shd.resolve("batch", None, "heads") == P("data", None,
                                                        "model")
        assert shd.resolve("fsdp", "model") == P("data", "model")


def test_resolve_multi_axis_batch():
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    with shd.axis_rules(mesh):
        spec = shd.resolve("batch")
        assert spec == P(("pod", "data"))


def test_serve_rules_disable_fsdp():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with shd.axis_rules(mesh, shd.SERVE_RULES):
        assert shd.resolve("fsdp") == P(None)
        assert shd.resolve("batch") == P("data")


def test_named_safe_drops_nondivisible():
    mesh = jax.make_mesh((1,), ("data",))
    with shd.axis_rules(mesh):
        # vocab=7 on a 1-way axis always divides; use a fake 2-way rule via
        # named_safe's divisibility math directly
        s = shd.named_safe(P("batch"), (4,))
        assert isinstance(s, jax.sharding.NamedSharding)


def test_param_spec_policy():
    ps = shd.param_spec(("layers", "attn", "wq"), (8, 64, 64))
    assert ps == P(None, "fsdp", "model")       # stacked layer dim first
    ps = shd.param_spec(("layers", "attn", "wo"), (8, 64, 64))
    assert ps == P(None, "model", "fsdp")
    ps = shd.param_spec(("embed",), (1000, 64))
    assert ps == P("vocab", "fsdp")
    ps = shd.param_spec(("lm_head",), (64, 1000))
    assert ps == P("fsdp", "vocab")
    # MoE expert tensors: experts on their own axis
    ps = shd.param_spec(("layers", "moe", "wi"), (8, 16, 64, 128))
    assert ps == P(None, "experts", "fsdp", None)
    # 1-D scales replicated
    ps = shd.param_spec(("layers", "ln1"), (8, 64))
    assert ps == P(None, None)


def test_constrain_under_mesh_runs():
    mesh = make_mesh((1,), ("data",))
    with shd.axis_rules(mesh):
        f = jax.jit(lambda x: shd.constrain(x * 2, "batch", None))
        out = f(jnp.ones((2, 3)))
        np.testing.assert_allclose(np.asarray(out), 2.0)


def test_make_production_mesh_requires_devices():
    """The suite session exposes 8 virtual CPU devices (conftest) — far
    short of the 256/512-chip production meshes, which must be built in
    a subprocess with placeholder devices (launch/dryrun.py does this);
    here we assert the constructor shape logic via the error path."""
    with pytest.raises(ValueError):
        make_production_mesh()            # 256 devices unavailable
    with pytest.raises(ValueError):
        make_production_mesh(multi_pod=True)


# ---------------- shard_map shim ----------------

def test_shard_map_shim_prefers_new_api(monkeypatch):
    """The shim calls ``jax.shard_map``, forwarding the ``check_vma``
    knob, never the legacy ``check_rep``."""
    seen = {}

    def fake(f, *, mesh, in_specs, out_specs, **kw):
        seen.update(kw, mesh=mesh)
        return f

    monkeypatch.setattr(jax, "shard_map", fake, raising=False)
    mesh = jax.make_mesh((2,), ("data",))
    out = shd.shard_map(lambda x: x, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=True)
    assert out(7) == 7
    assert seen == {"check_vma": True, "mesh": mesh}
    assert "check_rep" not in seen


def test_shard_map_shim_executes_on_data_mesh():
    """The shim's output matches the plain computation exactly on a real
    2-device mesh."""
    mesh = jax.make_mesh((2,), ("data",))
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 6)).astype(np.float32)
    fn = shd.shard_map(jnp.tanh, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)),
                                  np.asarray(jax.jit(jnp.tanh)(x)))


# ---------------- rule resolution vs missing mesh axes ----------------

def test_resolve_missing_candidate_axis_is_unconstrained():
    """A mesh lacking every candidate axis of a rule resolves to the
    unconstrained spec — same as an empty rule — while rules whose axis
    IS present still bind."""
    mesh = jax.make_mesh((1,), ("model",))
    with shd.axis_rules(mesh):
        assert shd.resolve("batch") == P(None)   # candidates (pod, data) absent
        assert shd.resolve("seq") == P(None)     # empty rule
        assert shd.resolve("heads") == P("model")
        assert shd.resolve("batch", "heads") == P(None, "model")


def test_lane_mesh_bounds_and_axis():
    from repro.launch.mesh import make_lane_mesh
    with pytest.raises(ValueError):
        make_lane_mesh(0)
    with pytest.raises(ValueError):
        make_lane_mesh(jax.device_count() + 1)
    mesh = make_lane_mesh(2)
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == 2
