"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from numpy.testing import assert_allclose

from repro.kernels import (demo_spheres, flash_attention, gaussian_blur,
                           gaussian_blur_halo, linear_attention, mandelbrot,
                           matmul, rap, raytrace, ref, taylor_sin)

rng = np.random.default_rng(42)


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (300, 200, 260),
                                   (128, 512, 128), (37, 129, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(m, k, n, dtype):
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    got = matmul(a, b, bm=128, bn=128, bk=128)
    want = ref.matmul(a, b)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert got.dtype == want.dtype
    assert_allclose(np.asarray(got, np.float32),
                    np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("h,w,bm", [(64, 128, 16), (200, 256, 64),
                                    (33, 130, 128)])
def test_gaussian(h, w, bm):
    img = jnp.asarray(rng.normal(size=(h, w)), jnp.float32)
    assert_allclose(gaussian_blur(img, bm=bm), ref.gaussian_blur(img),
                    rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h,w,bm,top", [(128, 256, 64, 0),
                                        (256, 5120, 128, 40),
                                        (64, 130, 16, 9), (120, 128, 128, 3)])
def test_gaussian_halo(h, w, bm, top):
    """The data plane's entry blurs rows ``top .. top + h`` of a larger
    image from those rows and two of context on either side (zeros past
    the image's edges), as the whole-image reference does. The piece has
    ``h + 4`` rows, so the last block's 8-row halo block runs past it."""
    full = rng.normal(size=(top + h + 5, w))
    img = jnp.asarray(np.pad(full, ((2, 2), (0, 0))), jnp.float32)
    piece = img[top:top + h + 4]
    want = ref.gaussian_blur(img[2:-2])[top:top + h]
    assert_allclose(gaussian_blur_halo(piece, bm=bm), want,
                    rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,terms", [(100, 8), (1000, 12), (4096, 16)])
def test_taylor(n, terms):
    x = jnp.asarray(rng.uniform(-3, 3, size=(n,)), jnp.float32)
    assert_allclose(taylor_sin(x, terms=terms, bm=4),
                    ref.taylor_sin(x, terms=terms), rtol=1e-5, atol=1e-6)
    if terms >= 12:
        assert_allclose(taylor_sin(x, terms=terms), np.sin(x),
                        rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("side,it", [(32, 32), (64, 48)])
def test_mandelbrot(side, it):
    re_ = np.linspace(-2.2, 0.8, side, dtype=np.float32)
    im = np.linspace(-1.4, 1.4, side, dtype=np.float32)
    cre, cim = [jnp.asarray(g) for g in np.meshgrid(re_, im)]
    got = mandelbrot(cre, cim, max_iter=it, bm=8)
    want = ref.mandelbrot(cre, cim, max_iter=it)
    assert_allclose(got, want, atol=0)


@pytest.mark.parametrize("n,spheres", [(1000, 4), (4000, 8)])
def test_raytrace(n, spheres):
    dx, dy = rng.uniform(-.4, .4, (2, n)).astype(np.float32)
    dz = np.sqrt(np.maximum(1 - dx**2 - dy**2, .5)).astype(np.float32)
    sph = demo_spheres(spheres)
    got = raytrace(jnp.asarray(dx), jnp.asarray(dy), jnp.asarray(dz),
                   sph, bm=8)
    want = ref.raytrace(jnp.asarray(dx), jnp.asarray(dy),
                        jnp.asarray(dz), sph)
    assert_allclose(got, want, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n,l", [(100, 32), (500, 96)])
def test_rap(n, l):
    vals = jnp.asarray(rng.normal(size=(n, l)), jnp.float32)
    lens = jnp.asarray(rng.integers(0, l + 1, size=(n,)), jnp.int32)
    assert_allclose(rap(vals, lens, bm=64), ref.rap(vals, lens),
                    rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
def test_flash_attention(hq, hkv, causal, window):
    B, T, D = 2, 128, 64
    q = jnp.asarray(rng.normal(size=(B, hq, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, hkv, T, D)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64)
    want = ref.attention(q, k, v, causal=causal, window=window)
    assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,chunk", [(64, 64), (200, 64), (256, 128)])
@pytest.mark.parametrize("dk,dv", [(16, 16), (32, 48)])
def test_linear_attention(t, chunk, dk, dv):
    BH = 3
    q = jnp.asarray(rng.normal(size=(BH, t, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, t, dk)) * 0.2, jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, t, dv)), jnp.float32)
    ld = jnp.asarray(-np.abs(rng.normal(size=(BH, t)) * 0.1), jnp.float32)
    got = linear_attention(q, k, v, ld, chunk=chunk)
    want = ref.linear_attention(q, k, v, ld)
    assert_allclose(got, want, rtol=3e-4, atol=3e-4)
    # chunked jnp twin (training path) matches too
    got2 = ref.chunked_linear_attention(q, k, v, ld, chunk=chunk)
    assert_allclose(got2, want, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    B, T, D = 1, 128, 128
    q = jnp.asarray(rng.normal(size=(B, 4, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, 2, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, 2, T, D)), jnp.bfloat16)
    got = flash_attention(q, k, v)
    want = ref.attention(q, k, v)
    assert got.dtype == jnp.bfloat16
    assert_allclose(np.asarray(got, np.float32),
                    np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)
