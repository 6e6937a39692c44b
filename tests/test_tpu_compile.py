"""Compile every served Pallas kernel for a TPU v5e chip, without a chip.

Interpret mode (what the rest of the suite runs) accepts programs the
TPU's compiler (Mosaic) refuses: a value sliced at a traced offset, a
block that breaks the (8, 128) tiling rule.  These tests lower the
kernels ``chip_smoke.py`` serves with ``interpret=False`` for a
described ``v5e:2x2`` topology, at the shapes the smoke run serves, and
check that each compiled program holds the kernel (``tpu_custom_call``)
under the name its ``pallas_call`` gives it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the fixture keeps
that to the worker the file runs on.
"""
import functools
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels as K

SMALL = (8, 12, 16, 24, 32)

# (pipeline, expected variant, lanes, per-lane arg shapes) — the buckets
# chip_smoke.py serves: the slot phase at lanes=8, the large phase at
# lanes=2, and the pusch_receive stages and chain at lanes=8
CASES = (
    [("cholesky_solve", "base", 8, ((n, n), (n, 2))) for n in SMALL]
    + [("qr_solve", "base", 8, ((n + 4, n), (n + 4, 2))) for n in SMALL]
    + [("mmse_equalize", "base", 8, ((20, 16), (20, 2))),
       ("mmse_equalize", "split_complex", 8,
        ((20, 16), (20, 16), (20, 2), (20, 2))),
       ("cholesky_solve", "blocked", 2, ((256, 256), (256, 2))),
       ("qr_solve", "blocked", 2, ((272, 256), (272, 2))),
       ("mmse_equalize", "base", 2, ((272, 256), (272, 2)))]
    + [("cholesky_solve", "tiled", 2, ((n, n), (n, 2))) for n in (512, 1024)]
    + [(p, "tiled", 2, ((n + 16, n), (n + 16, 2)))
       for p in ("qr_solve", "mmse_equalize") for n in (512, 1024)]
    + [c for n in (8, 12) for c in (
        ("pusch_fft", "base", 8, ((n + 4, 64), (n + 4, 64))),
        ("pusch_chanest", "base", 8, ((n, 2 * n), (n + 4, 2 * n))),
        ("mmse_equalize", "base", 8, ((n + 4, n), (n + 4, 2))),
        ("pusch_chain", "base", 8,
         ((n, 2 * n), (n + 4, 2 * n), (n + 4, 2))))]
)

# tiled buckets at an n that 128 does not divide, which the entry points
# pad up to whole 128-wide slabs (n = 704 runs at 768): the STAP bucket
# chipbench serves (KASSPER's 352 complex DOF, real-embedded, 32
# steering vectors, 8 lanes), and the tall QR and MMSE systems
PADDED_CASES = (
    [("cholesky_solve", "tiled", 8, ((704, 704), (704, 32)))]
    + [(p, "tiled", 2, ((720, 704), (720, 2)))
       for p in ("qr_solve", "mmse_equalize")]
)

# the NR cell's split_complex MMSE bucket at the batch sizes a bucket
# flush's launch plan sends (2, 16 and 32 lane groups of 8; 64 antennas,
# 16 layers, 144 right-hand sides)
NR_SHAPES = ((64, 16), (64, 16), (64, 144), (64, 144))
PLAN_CASES = [("mmse_equalize", "split_complex", lanes, NR_SHAPES)
              for lanes in (16, 128, 256)]


# the name each served kernel's pallas_call gives its custom call, after
# its registry entry or variant; the profiler's trace names the device op
# by it
KERNEL_NAMES = {
    ("cholesky_solve", "base"): "cholesky_solve",
    ("cholesky_solve", "blocked"): "cholesky_solve_blocked",
    ("cholesky_solve", "tiled"): "cholesky_solve_tiled",
    ("qr_solve", "base"): "qr_solve",
    ("qr_solve", "blocked"): "qr_solve_blocked",
    ("qr_solve", "tiled"): "qr_solve_tiled",
    ("mmse_equalize", "base"): "mmse_equalize",
    ("mmse_equalize", "split_complex"): "mmse_split",
    ("mmse_equalize", "tiled"): "mmse_equalize_tiled",
    ("pusch_fft", "base"): "fft",
    ("pusch_chanest", "base"): "pusch_chanest",
    ("pusch_chain", "base"): "pusch_chain",
}


def _case_id(case):
    pipeline, variant, lanes, shapes = case
    return f"{pipeline}-{variant}-{'x'.join(map(str, shapes[0]))}-l{lanes}"


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described (not attached) v5e:2x2 host, with JAX's
    persistent compilation cache off: what is compiled for a described
    chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("case", CASES + PADDED_CASES + PLAN_CASES,
                         ids=_case_id)
def test_served_kernel_compiles_for_v5e(one_chip, case, record_property):
    pipeline, variant, lanes, shapes = case
    spec = K.get(pipeline)
    v = spec.dispatch_key(shapes, (np.float32,) * len(shapes))
    assert v.name == variant, (pipeline, shapes, v.name)
    args = [jax.ShapeDtypeStruct((lanes, *s), np.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(functools.partial(v.fn, interpret=False)) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    name = KERNEL_NAMES[pipeline, variant]
    assert re.search(rf'%{name}(\.\d+)? = [^\n]*custom-call\([^\n]*'
                     rf'custom_call_target="tpu_custom_call"', text), name
    if case in PADDED_CASES:
        # the kernel runs at whole 128-wide slabs, not at the job's n
        run = v.run_shapes(shapes)[0]
        assert run[1] % 128 == 0 and run[1] > shapes[0][1], run
        assert re.search(rf'%{name}(\.\d+)? = [^\n]*'
                         rf'f32\[{lanes},{run[0]},{run[1]}\]', text), run
    mem = compiled.memory_analysis()
    record_property("memory_analysis", str(mem))
    # the program fits one chip's 16 GB of HBM with room to spare
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("lanes", (8, 16, 128, 256))
def test_served_mmse_split_entry_point_holds_one_kernel_op(one_chip, lanes):
    """The NR bucket's entry point as ``SolverMux`` serves it, at the
    batch sizes of the launch plan: the kernel is the program's only
    instruction that names it (the benchmark's device trace finds the
    kernel by its name), with no relayout copy of the answer, whose
    default layout puts the batch dimension minor from 128 lanes on."""
    from repro.serve.solver import VariantDispatcher
    spec = K.get("mmse_equalize")
    key = tuple((s, "float32") for s in NR_SHAPES)
    variant, fn = VariantDispatcher(
        spec, {"interpret": False, "sigma2": 0.1}).resolve(key)
    assert variant.name == "split_complex"
    args = [jax.ShapeDtypeStruct((lanes, *s), np.float32, sharding=one_chip)
            for s in NR_SHAPES]
    text = fn.lower(*args).compile().as_text()
    naming = [line for line in text.splitlines()
              if "mmse_split" in line and " = " in line]
    assert len(naming) == 1, naming
    assert re.search(r'%mmse_split\.\d+ = f32\[' + str(lanes)
                     + r',32,144\][^\n]*custom_call_target="tpu_custom_call"',
                     naming[0])


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The described v5e:2x2 host's four chips as a ``SolverMux`` lane
    mesh."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from repro.serve import LaneShards
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return LaneShards(Mesh(np.asarray(topo.devices[:4]), ("data",)))


@pytest.mark.parametrize("lanes", (8, 32, 256))
def test_spanning_mmse_split_entry_point_holds_one_kernel_op(four_chips,
                                                             lanes):
    """The NR bucket's mesh-spanning entry point on four chips, at the
    lanes a chip of the four-carrier cell's launches (one unit, and the
    plan's 4 and 32 units): each chip's program names the kernel as the
    one-chip program does, and no relayout copy of the answer takes its
    name, so the benchmark's device trace finds one kernel op."""
    from repro.serve.solver import VariantDispatcher
    spec = K.get("mmse_equalize")
    key = tuple((s, "float32") for s in NR_SHAPES)
    variant, fn = VariantDispatcher(
        spec, {"interpret": False, "sigma2": 0.1},
        shards=four_chips).resolve_sharded(key)
    assert variant.name == "split_complex"
    args = [jax.ShapeDtypeStruct((4 * lanes, *s), np.float32,
                                 sharding=four_chips.sharding)
            for s in NR_SHAPES]
    text = fn.lower(*args).compile().as_text()
    naming = [line for line in text.splitlines()
              if "mmse_split" in line and " = " in line]
    assert len(naming) == 1, naming
    assert re.search(r'%mmse_split\.1 = f32\[' + str(lanes)
                     + r',32,144\][^\n]*custom_call_target="tpu_custom_call"',
                     naming[0])
