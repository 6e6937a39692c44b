"""Launcher entrypoints: distributed train (mesh+shardings+resume) and
serve (TP rules) on a 1x1 mesh."""
import types

import numpy as np
import pytest

from repro.launch import train as LT


def _args(tmp_path, steps):
    return types.SimpleNamespace(
        arch="xlstm-125m", smoke=True, mesh="1x1", steps=steps,
        seq=32, batch=4, lr=1e-3, seed=0, ckpt=str(tmp_path),
        ckpt_every=4)


def test_launch_train_runs_and_resumes(tmp_path):
    out = LT.run(_args(tmp_path, 4))
    assert len(out["losses"]) == 4
    assert np.isfinite(out["losses"]).all()
    # resume: extending to 6 steps only runs the remaining 2
    out2 = LT.run(_args(tmp_path, 6))
    assert len(out2["losses"]) == 2


def test_launch_mesh_parse():
    mesh = LT.make_mesh("1x1")
    assert mesh.axis_names == ("data", "model")


def test_launch_mesh_axes_are_auto():
    """Launcher meshes use Auto axes, under which the logical-axis
    sharding constraints apply (JAX 0.9 defaults to Explicit)."""
    from jax.sharding import AxisType
    assert LT.make_mesh("1x1").axis_types == (AxisType.Auto,) * 2


@pytest.mark.parametrize("env_dir", [None, "outside"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to the fixed <repo>/.jax_cache."""
    import os

    import jax

    from repro.launch import xla_env
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = xla_env.setup_compile_cache()
        if env_dir is None:
            assert got == xla_env.CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == got
            repo = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(repo, ".jax_cache")
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_host_device_count_is_cpu_only(monkeypatch):
    import os

    from repro.launch import xla_env
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert not xla_env.force_host_device_count(8)
    assert "XLA_FLAGS" not in os.environ
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert xla_env.force_host_device_count(8)
