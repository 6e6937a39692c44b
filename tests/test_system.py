"""System-level behaviour: shape-cell policy, abstract specs, and a
subprocess SPMD lower+compile on a small placeholder mesh (the same code
path the 256/512-chip dry-run uses, scaled down to stay fast)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.launch import shapes as shp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_shape_cells_cover_assignment():
    assert set(shp.SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                               "long_500k"}
    assert shp.SHAPES["train_4k"] == dict(kind="train", seq=4096,
                                          batch=256)
    assert shp.SHAPES["long_500k"] == dict(kind="decode", seq=524288,
                                           batch=1)


def test_long_context_applicability():
    """long_500k only for sub-quadratic archs (DESIGN.md §Arch-applic.)."""
    runs = {a for a in ARCHS
            if shp.cell_applicable(get_config(a), "long_500k")[0]}
    assert runs == {"zamba2-2.7b", "xlstm-125m"}
    # every other (arch, shape) cell is applicable
    for a in ARCHS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shp.cell_applicable(get_config(a), s)[0]


def test_abstract_params_no_allocation():
    """ShapeDtypeStruct stand-ins: full 132B config stays abstract."""
    cfg = get_config("dbrx-132b")
    p = shp.abstract_params(cfg)
    leaves = jax.tree.leaves(p)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    total = sum(int(np.prod(l.shape)) for l in leaves)
    assert total > 1e11          # it really is the 132B model


def test_batch_specs_families():
    cfg = get_config("internvl2-76b")
    b = shp.batch_specs(cfg, 4096, 256, labels=True)
    assert b["tokens"].shape == (256, 4096)
    assert "vision_embeds" in b
    cfg = get_config("seamless-m4t-large-v2")
    b = shp.batch_specs(cfg, 32768, 32, labels=False)
    assert "src_embeds" in b and "labels" not in b


def test_abstract_cache_decode_shapes():
    cfg = get_config("qwen3-14b")
    c = shp.abstract_cache(cfg, 128, 32768)
    assert c["k"].shape == (40, 128, 32768, 8, 128)
    cfg = get_config("xlstm-125m")
    c = shp.abstract_cache(cfg, 1, 524288)
    # O(1) state: no sequence-length dimension anywhere
    assert all(524288 not in l.shape for l in jax.tree.leaves(c))


@pytest.mark.slow
def test_spmd_lower_compile_small_mesh():
    """The production sharding rules compile under SPMD on an 8-device
    placeholder mesh (subprocess so the 1-device test session is safe)."""
    prog = textwrap.dedent("""
        from repro.launch.xla_env import force_host_device_count
        force_host_device_count(8)
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke
        from repro.distributed import sharding as shd
        from repro.launch import shapes as shp
        from repro.optim.optimizer import OptConfig
        from repro.train.trainer import make_train_step

        cfg = get_smoke("qwen3-14b")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with shd.axis_rules(mesh):
            p_abs = shp.abstract_params(cfg)
            import jax.tree_util as jtu
            p_sh = jtu.tree_map_with_path(
                lambda path, l: shd.named_safe(
                    shd.param_spec(tuple(getattr(k, "key", str(k))
                                         for k in path), l.shape), l.shape),
                p_abs)
            b_abs = shp.batch_specs(cfg, 64, 8, labels=True)
            b_sh = jax.tree.map(
                lambda l: shd.named_safe(
                    P("data", *([None] * (len(l.shape) - 1))), l.shape),
                b_abs)
            opt_abs = {"m": p_abs, "v": p_abs,
                       "step": jax.ShapeDtypeStruct((), jnp.int32)}
            opt_sh = {"m": p_sh, "v": p_sh, "step": shd.named(P())}
            fn = make_train_step(cfg, OptConfig())
            comp = jax.jit(fn, in_shardings=(p_sh, opt_sh, b_sh),
                           out_shardings=(p_sh, opt_sh, None)) \\
                .lower(p_abs, opt_abs, b_abs).compile()
            m = comp.memory_analysis()
            print("OK", m.temp_size_in_bytes >= 0)
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
