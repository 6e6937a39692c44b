"""Roofline machinery: HLO roll-up parser (scan trip counts, dot flops,
collective bytes) validated against known-cost jitted programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (Roofline, collective_bytes,
                                     model_flops_decode, model_flops_train,
                                     peaks)
from repro.roofline.hlo_costs import analyze_hlo
from repro.configs import get_config


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_counted():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    txt = compiled_text(lambda a, b: a @ b, a, b)
    got = analyze_hlo(txt)
    want = 2 * 64 * 128 * 32
    assert got["flops"] == pytest.approx(want, rel=0.01)


def test_scan_trip_count_multiplies():
    """cost_analysis visits a while body once; the roll-up must multiply
    by the trip count (this is why the parser exists)."""
    a = jnp.zeros((32, 32), jnp.float32)
    n_steps = 11

    def f(a):
        def step(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(step, a, None, length=n_steps)
        return out

    got = analyze_hlo(compiled_text(f, a))
    want = 2 * 32 * 32 * 32 * n_steps
    assert got["flops"] == pytest.approx(want, rel=0.05)
    assert n_steps in got["trips"].values()


def test_bytes_nonzero_and_bounded():
    a = jnp.zeros((256, 256), jnp.float32)
    got = analyze_hlo(compiled_text(lambda a: a + 1.0, a))
    nbytes = 256 * 256 * 4
    assert got["bytes"] >= 2 * nbytes * 0.9        # read + write
    assert got["bytes"] <= 6 * nbytes              # fused: no blowup


def test_collective_bytes_parser():
    hlo = """
ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %p = f32[128,256] parameter(0)
  %ag = f32[256,256] all-gather(%p), dimensions={0}
  %ar = f32[128,256] all-reduce(%p), to_apply=%add
  %cp = f32[128,256] collective-permute(%p), source_target_pairs={{0,1}}
}
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 256 * 256 * 4
    assert got["all-reduce"] == 2 * 128 * 256 * 4   # 2x ring factor
    assert got["collective-permute"] == 128 * 256 * 4


def test_analyze_hlo_collectives_roll_up():
    hlo = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64] parameter(0)
  ROOT %ar = f32[64] all-reduce(%p), to_apply=%add
}
"""
    got = analyze_hlo(hlo)
    assert got["collectives"]["all-reduce"] == 2 * 64 * 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(arch="x", shape="y", mesh="single", chips=1,
                 hlo_flops=197e12, hlo_bytes=819e9 * 2,
                 coll_bytes=50e9 * 0.5, coll_breakdown={},
                 model_flops=98.5e12)
    r.finish()
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.useful_ratio == pytest.approx(0.5)
    assert r.step_time == pytest.approx(2.0)
    assert r.mfu == pytest.approx(98.5e12 / (197e12 * 2.0))


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the device-kind table; an unknown kind raises
    instead of silently borrowing another chip's peaks."""
    assert peaks("TPU v5 lite").flops == pytest.approx(197e12)
    r = Roofline(arch="x", shape="y", mesh="single", chips=1,
                 hlo_flops=1.0, hlo_bytes=1.0, coll_bytes=0.0,
                 coll_breakdown={}, model_flops=1.0, device_kind="cpu")
    with pytest.raises(KeyError):
        r.finish()


def test_model_flops_formulas():
    cfg = get_config("phi4-mini-3.8b")
    n = cfg.active_param_count()
    assert model_flops_train(cfg, 1000) == pytest.approx(6.0 * n * 1000)
    d = model_flops_decode(cfg, batch=8, ctx=4096)
    assert d > 2.0 * n * 8                       # attention term added
    # MoE: active (not total) params enter the formula
    moe = get_config("dbrx-132b")
    assert model_flops_train(moe, 1) < 6.0 * moe.param_count()


def test_rollup_vs_cost_analysis_on_scanned_model():
    """End-to-end: the roll-up flops for a scanned 2-layer MLP are ~2x the
    single-layer flops, while naive cost_analysis undercounts."""
    w = jnp.zeros((2, 64, 64), jnp.float32)   # 2 stacked layers
    x = jnp.zeros((8, 64), jnp.float32)

    def f(w, x):
        def step(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(step, x, w)
        return h

    per_layer = 2 * 8 * 64 * 64
    got = analyze_hlo(compiled_text(f, w, x))
    assert got["flops"] == pytest.approx(2 * per_layer, rel=0.05)
