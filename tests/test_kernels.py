"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle in
ref.py, swept over shapes and dtypes.  These are the paper's seven DSP
workloads + the two LM-side kernels (flash attention, SSM scan).

Kernels are fetched from the registry (repro.kernels.get) — the single
enumeration point — instead of a hand-maintained import list; the
registry-driven auto-discovery sweep lives in test_pipelines.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ref as ref
from repro import kernels as K

from conftest import assert_close

cholesky_pallas = K.get("cholesky").pallas
trisolve_pallas = K.get("trisolve").pallas
qr_pallas = K.get("qr").pallas
svd_pallas = K.get("svd").pallas
gemm_pallas = K.get("gemm").pallas
fir_pallas = K.get("fir").pallas
fft_pallas = K.get("fft").pallas
flash_attention_pallas = K.get("flash_attention").pallas
ssm_scan_pallas = K.get("ssm_scan").pallas

RNG = np.random.default_rng(1234)


def spd(b, n, dtype=np.float32):
    a = RNG.standard_normal((b, n, n)).astype(dtype)
    return a @ a.swapaxes(-1, -2) + n * np.eye(n, dtype=dtype)


# ---------------- cholesky ----------------

@pytest.mark.parametrize("n", [8, 12, 16, 24, 32])
@pytest.mark.parametrize("b", [1, 3])
def test_cholesky_sizes(n, b):
    """Paper's data sizes 12..32 (non-power-of-two included)."""
    a = spd(b, n)
    got = cholesky_pallas(a, interpret=True)
    assert_close(got, ref.cholesky(a), rtol=1e-4, name=f"chol{n}")


def test_cholesky_reconstruction():
    a = spd(2, 16)
    l = np.asarray(cholesky_pallas(a, interpret=True))
    assert_close(l @ l.swapaxes(-1, -2), a, rtol=1e-4, name="LL^T")
    # strictly lower-triangular output
    assert np.allclose(np.triu(l, 1), 0.0)


# ---------------- trisolve ----------------

@pytest.mark.parametrize("n,m", [(8, 1), (12, 4), (16, 8), (32, 2)])
def test_trisolve_sizes(n, m):
    a = spd(2, n)
    l = np.linalg.cholesky(a)
    b = RNG.standard_normal((2, n, m)).astype(np.float32)
    got = trisolve_pallas(l, b, interpret=True)
    assert_close(got, ref.trisolve(l, b), rtol=1e-3, name=f"tri{n}x{m}")
    # residual check: L @ x == b
    assert_close(l @ np.asarray(got), b, rtol=1e-3, name="residual")


# ---------------- QR ----------------

@pytest.mark.parametrize("m,n", [(12, 12), (16, 12), (24, 16), (32, 32)])
def test_qr_sizes(m, n):
    a = RNG.standard_normal((2, m, n)).astype(np.float32)
    q, r = qr_pallas(a, interpret=True)
    q, r = np.asarray(q), np.asarray(r)
    assert_close(q @ r, a, rtol=1e-4, name="QR=A")
    eye = np.broadcast_to(np.eye(m, dtype=np.float32), (2, m, m))
    assert_close(q @ q.swapaxes(-1, -2), eye, rtol=1e-4, name="QQ^T")
    # R upper triangular
    assert np.allclose(np.tril(r[:, :, :], -1), 0.0, atol=1e-4)


# ---------------- SVD ----------------

@pytest.mark.parametrize("m,n", [(12, 12), (16, 12), (32, 24)])
def test_svd_singular_values(m, n):
    a = RNG.standard_normal((2, m, n)).astype(np.float32)
    u, s, v = svd_pallas(a, sweeps=14, interpret=True)
    want = np.linalg.svd(a, compute_uv=False)
    got = np.sort(np.asarray(s), axis=-1)[:, ::-1]
    assert_close(got, want, rtol=1e-3, name="sigma")


def test_svd_reconstruction():
    a = RNG.standard_normal((1, 16, 12)).astype(np.float32)
    u, s, v = svd_pallas(a, sweeps=14, interpret=True)
    u, s, v = np.asarray(u), np.asarray(s), np.asarray(v)
    assert_close((u * s[:, None, :]) @ v.swapaxes(-1, -2), a, rtol=1e-3,
                 name="USV^T")


# ---------------- GEMM ----------------

@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (64, 64, 64, 64, 64, 64),
    (128, 64, 128, 64, 128, 64),
    (128, 128, 128, 128, 128, 128),
    (256, 128, 128, 128, 128, 128),
])
def test_gemm_blocks(m, k, n, bm, bn, bk):
    x = RNG.standard_normal((m, k)).astype(np.float32)
    y = RNG.standard_normal((k, n)).astype(np.float32)
    got = gemm_pallas(jnp.asarray(x), jnp.asarray(y), bm=bm, bn=bn, bk=bk,
                      interpret=True)
    assert_close(got, x @ y, rtol=1e-4, name="gemm")


def test_gemm_bf16():
    x = RNG.standard_normal((64, 64)).astype(np.float32)
    y = RNG.standard_normal((64, 64)).astype(np.float32)
    got = gemm_pallas(jnp.asarray(x, jnp.bfloat16),
                      jnp.asarray(y, jnp.bfloat16),
                      bm=64, bn=64, bk=64, interpret=True)
    assert_close(np.asarray(got, np.float32), x @ y, rtol=5e-2,
                 name="gemm-bf16")


# ---------------- FIR ----------------

@pytest.mark.parametrize("n,m", [(128, 9), (256, 31), (512, 65)])
def test_fir_centro_symmetric(n, m):
    x = RNG.standard_normal((n,)).astype(np.float32)
    h = RNG.standard_normal((m,)).astype(np.float32)
    h = (h + h[::-1]) / 2          # centro-symmetric taps (paper workload)
    out = n - m + 1
    got = fir_pallas(jnp.asarray(x), jnp.asarray(h), bo=out,
                     interpret=True)
    assert_close(got[:out], ref.fir(x, h), rtol=1e-4, name=f"fir{n},{m}")


# ---------------- FFT ----------------

@pytest.mark.parametrize("n", [64, 128, 1024])
def test_fft_sizes(n):
    """Paper's FFT sizes 64/128/1024."""
    xr = RNG.standard_normal((2, n)).astype(np.float32)
    xi = RNG.standard_normal((2, n)).astype(np.float32)
    fre, fim = fft_pallas(xr, xi, interpret=True)
    wre, wim = ref.fft(xr, xi)
    assert_close(np.stack([np.asarray(fre), np.asarray(fim)]),
                 np.stack([np.asarray(wre), np.asarray(wim)]),
                 rtol=1e-3, name=f"fft{n}")


def test_fft_matches_numpy():
    xr = RNG.standard_normal((1, 256)).astype(np.float32)
    xi = np.zeros((1, 256), np.float32)
    fre, fim = fft_pallas(xr, xi, interpret=True)
    want = np.fft.fft(xr[0])
    assert_close(np.asarray(fre)[0], want.real, rtol=1e-3, name="fft-re")
    assert_close(np.asarray(fim)[0], want.imag, rtol=1e-3, name="fft-im")


# ---------------- flash attention (inductive RI stream) ----------------

@pytest.mark.parametrize("s,dh,causal", [
    (128, 64, True), (256, 64, True), (128, 128, True), (128, 64, False),
])
def test_flash_attention(s, dh, causal):
    q = (RNG.standard_normal((2, 2, s, dh)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((2, 2, s, dh)) * 0.3).astype(np.float32)
    v = RNG.standard_normal((2, 2, s, dh)).astype(np.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    assert_close(got, ref.mha(q, k, v, causal=causal), rtol=1e-3,
                 name="flash")


def test_flash_attention_bf16():
    s, dh = 128, 64
    q = (RNG.standard_normal((1, 2, s, dh)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((1, 2, s, dh)) * 0.3).astype(np.float32)
    v = RNG.standard_normal((1, 2, s, dh)).astype(np.float32)
    got = flash_attention_pallas(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), causal=True, interpret=True)
    assert_close(np.asarray(got, np.float32),
                 ref.mha(q, k, v, causal=True), rtol=5e-2,
                 name="flash-bf16")


def test_flash_attention_small_blocks():
    """Block sizes smaller than seq exercise the inductive kv trip count
    (kv blocks visited = q_block + 1 — the RI stream)."""
    s, dh = 256, 64
    q = (RNG.standard_normal((1, 1, s, dh)) * 0.3).astype(np.float32)
    k = (RNG.standard_normal((1, 1, s, dh)) * 0.3).astype(np.float32)
    v = RNG.standard_normal((1, 1, s, dh)).astype(np.float32)
    got = flash_attention_pallas(q, k, v, causal=True, bq=64, bkv=64,
                                 interpret=True)
    assert_close(got, ref.mha(q, k, v, causal=True), rtol=1e-3,
                 name="flash-blk")


# ---------------- SSM chunked scan (ordered inter-chunk dep) ----------

@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (128, 128)])
def test_ssm_scan_shared_bc(s, chunk):
    b, h, n, p = 1, 4, 8, 8
    x = RNG.standard_normal((b, h, s, p)).astype(np.float32)
    a = RNG.uniform(0.8, 0.999, (b, h, s)).astype(np.float32)
    bb = RNG.standard_normal((b, s, n)).astype(np.float32)
    cc = RNG.standard_normal((b, s, n)).astype(np.float32)
    y, hf = ssm_scan_pallas(x, a, bb, cc, chunk=chunk, interpret=True)
    yw, hw = ref.ssm_scan(np.moveaxis(x, 1, 2), np.moveaxis(a, 1, 2),
                          bb, cc)
    assert_close(np.moveaxis(np.asarray(y), 1, 2), yw, rtol=1e-3,
                 name="ssm-y")
    assert_close(hf, hw, rtol=1e-3, name="ssm-h")


def test_ssm_scan_per_head_bc():
    b, h, s, n, p = 1, 2, 64, 8, 4
    x = RNG.standard_normal((b, h, s, p)).astype(np.float32)
    a = RNG.uniform(0.8, 0.999, (b, h, s)).astype(np.float32)
    bb = RNG.standard_normal((b, h, s, n)).astype(np.float32)
    cc = RNG.standard_normal((b, h, s, n)).astype(np.float32)
    y, hf = ssm_scan_pallas(x, a, bb, cc, chunk=16, interpret=True)
    yw, hw = ref.ssm_scan(np.moveaxis(x, 1, 2), np.moveaxis(a, 1, 2),
                          np.moveaxis(bb, 1, 2), np.moveaxis(cc, 1, 2))
    assert_close(np.moveaxis(np.asarray(y), 1, 2), yw, rtol=1e-3,
                 name="ssm-y-ph")
    assert_close(hf, hw, rtol=1e-3, name="ssm-h-ph")


def test_ssm_scan_chunk_invariance():
    """The ordered inter-chunk dependence must make the result independent
    of the chunk size (paper F1: ordering is what guarantees correctness)."""
    b, h, s, n, p = 1, 2, 128, 4, 4
    x = RNG.standard_normal((b, h, s, p)).astype(np.float32)
    a = RNG.uniform(0.9, 0.999, (b, h, s)).astype(np.float32)
    bb = RNG.standard_normal((b, s, n)).astype(np.float32)
    cc = RNG.standard_normal((b, s, n)).astype(np.float32)
    y16, _ = ssm_scan_pallas(x, a, bb, cc, chunk=16, interpret=True)
    y64, _ = ssm_scan_pallas(x, a, bb, cc, chunk=64, interpret=True)
    assert_close(y16, y64, rtol=1e-4, name="chunk-invariance")


def test_interpret_mode_never_hides_a_tpu(monkeypatch):
    """Interpret mode is the default off a TPU only; asking for it on a
    TPU raises instead of running the kernels in the interpreter."""
    from repro.kernels import common
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(common, "on_tpu", lambda: False)
    assert common.interpret_default()
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    assert not common.interpret_default()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError):
        common.interpret_default()
