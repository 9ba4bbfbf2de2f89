"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True on
CPU executes the kernel body in Python)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# sqdist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 256, 1000, 65536 + 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sqdist_sweep(n, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n))
    x = jax.random.normal(k1, (n,), dtype)
    r = jax.random.normal(k2, (n,), dtype)
    got = float(ops.sqdist(x, r, block=256))
    want = float(ref.sqdist_ref(x, r))
    assert np.isclose(got, want, rtol=1e-3), (got, want)


def test_sqdist_tree():
    k = jax.random.PRNGKey(0)
    a = {"w": jax.random.normal(k, (13, 7)), "b": jnp.ones((5,))}
    b = {"w": jnp.zeros((13, 7)), "b": jnp.zeros((5,))}
    got = float(ops.tree_sqdist(a, b, block=64))
    want = float(ref.sqdist_ref(a["w"], b["w"]) + ref.sqdist_ref(a["b"], b["b"]))
    assert np.isclose(got, want, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 4096), seed=st.integers(0, 1000))
def test_sqdist_property(n, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n,))
    r = jax.random.normal(k2, (n,))
    assert np.isclose(float(ops.sqdist(x, r, block=512)),
                      float(ref.sqdist_ref(x, r)), rtol=1e-4)


# ---------------------------------------------------------------------------
# batched sqdist over the flat fleet-plane: (m, P) x (P,) -> (m,)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(1, 1), (3, 7), (8, 256), (5, 1000),
                                 (17, 512 + 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sqdist_rows_sweep(m, n, dtype):
    """The fleet-plane grid variant vs the single-vector oracle, row by
    row — odd shapes exercise both the row and the column padding."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 1000 + n))
    X = jax.random.normal(k1, (m, n), dtype)
    r = jax.random.normal(k2, (n,), dtype)
    got = np.asarray(ops.sqdist_rows(X, r, block_m=4, block=256))
    assert got.shape == (m,)
    want = np.asarray(jax.vmap(lambda x: ref.sqdist_ref(x, r))(X))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_sqdist_rows_matches_scalar_kernel():
    """Row i of the batched kernel == the single-model kernel on row i."""
    k = jax.random.PRNGKey(0)
    X = jax.random.normal(k, (6, 777))
    r = jax.random.normal(jax.random.fold_in(k, 1), (777,))
    rows = np.asarray(ops.sqdist_rows(X, r, block_m=2, block=128))
    for i in range(6):
        one = float(ops.sqdist(X[i], r, block=128))
        assert np.isclose(rows[i], one, rtol=1e-5), i


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 12), n=st.integers(1, 2048),
       seed=st.integers(0, 1000))
def test_sqdist_rows_property(m, n, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(k1, (m, n))
    r = jax.random.normal(k2, (n,))
    got = np.asarray(ops.sqdist_rows(X, r, block_m=8, block=512))
    want = np.asarray(jax.vmap(lambda x: ref.sqdist_ref(x, r))(X))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("block_m,block,match", [
    (8, 100, "multiple of 128"),          # not a lane multiple
    (64, 65536, "VMEM"),                   # 2 x (64, 65536) f32 tiles
])
def test_sqdist_rows_rejects_tiles_the_chip_refuses(block_m, block, match):
    X = jnp.ones((128, 2 * 65536))
    r = jnp.zeros((2 * 65536,))
    with pytest.raises(ValueError, match=match):
        ops.sqdist_rows(X, r, block_m=block_m, block=block)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 5, 128), (130, 32), (1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(k1, shape, dtype)
    s = jax.random.normal(k2, (shape[-1],))
    got = np.asarray(ops.rmsnorm(x, s, block_rows=32), np.float32)
    want = np.asarray(ref.rmsnorm_ref(x, s), np.float32)
    np.testing.assert_allclose(got, want, **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Sq,Sk", [(64, 64), (100, 100), (32, 96), (1, 128)])
@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(Sq, Sk, window, dtype):
    k = jax.random.PRNGKey(Sq * 1000 + Sk + window)
    kq, kk, kv = jax.random.split(k, 3)
    B, d = 2, 32
    q = jax.random.normal(kq, (B, Sq, d), dtype)
    kk_ = jax.random.normal(kk, (B, Sk, d), dtype)
    v = jax.random.normal(kv, (B, Sk, d), dtype)
    got = np.asarray(ops.flash_attention(
        q, kk_, v, window=window, block_q=32, block_k=32), np.float32)
    want = np.asarray(ref.flash_attention_ref(
        q, kk_, v, window=window), np.float32)
    np.testing.assert_allclose(got, want, **_tol(dtype))


def test_flash_gqa_matches_ref():
    k = jax.random.PRNGKey(0)
    B, S, H, Hkv, d = 2, 64, 8, 2, 16
    q = jax.random.normal(k, (B, S, H, d))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, Hkv, d))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, Hkv, d))
    got = ops.flash_attention_gqa(q, kk, v, block_q=32, block_k=32)
    # reference: expand kv heads and run per-head dense attention
    G = H // Hkv
    kfull = jnp.repeat(kk, G, axis=2)
    vfull = jnp.repeat(v, G, axis=2)
    outs = []
    for h in range(H):
        outs.append(ref.flash_attention_ref(
            q[:, :, h], kfull[:, :, h], vfull[:, :, h]))
    want = jnp.stack(outs, axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(Sq=st.integers(2, 80), seed=st.integers(0, 100),
       window=st.sampled_from([0, 8, 33]))
def test_flash_property(Sq, seed, window):
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (1, Sq, 16))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (1, Sq, 16))
    v = jax.random.normal(jax.random.fold_in(k, 2), (1, Sq, 16))
    got = ops.flash_attention(q, kk, v, window=window, block_q=16, block_k=16)
    want = ref.flash_attention_ref(q, kk, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(64, 16), (96, 32), (100, 32), (8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(S, chunk, dtype):
    k = jax.random.PRNGKey(S + chunk)
    BH, P, N = 3, 8, 4
    x = jax.random.normal(k, (BH, S, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (BH, S)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 2), (BH,)))
    b = jax.random.normal(jax.random.fold_in(k, 3), (BH, S, N))
    c = jax.random.normal(jax.random.fold_in(k, 4), (BH, S, N))
    y, h = ops.ssd_scan(x, dt.astype(dtype), a, b.astype(dtype),
                        c.astype(dtype), chunk=chunk)
    yr, hr = ref.ssd_scan_ref(x, dt.astype(dtype), a, b.astype(dtype),
                              c.astype(dtype))
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), **tol)


def test_ssd_matches_model_mamba_forward():
    """The kernel agrees with the model's chunked-jnp SSD implementation."""
    from repro.models.mamba import _ssd_chunked
    k = jax.random.PRNGKey(5)
    Bb, S, H, P, G, N = 2, 64, 4, 8, 1, 16
    xh = jax.random.normal(k, (Bb, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (Bb, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 2), (H,)))
    B_ = jax.random.normal(jax.random.fold_in(k, 3), (Bb, S, G, N))
    C_ = jax.random.normal(jax.random.fold_in(k, 4), (Bb, S, G, N))
    y_model, h_model = _ssd_chunked(xh, dt, A, B_, C_, chunk=16)
    # kernel layout: (B*H, S, P) etc., groups pre-repeated
    xk = xh.transpose(0, 2, 1, 3).reshape(Bb * H, S, P)
    dtk = dt.transpose(0, 2, 1).reshape(Bb * H, S)
    ak = jnp.tile(A, (Bb,))
    rep = H // G
    Bk = jnp.repeat(B_, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bb * H, S, N)
    Ck = jnp.repeat(C_, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bb * H, S, N)
    y_k, h_k = ops.ssd_scan(xk, dtk, ak, Bk, Ck, chunk=16)
    y_k = y_k.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
    h_k = h_k.reshape(Bb, H, P, N)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_model),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_model),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# banded sliding-window attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,w", [(64, 16), (128, 32), (32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_sweep(S, w, dtype):
    from repro.kernels.swa_attention import swa_attention
    k0 = jax.random.PRNGKey(S + w)
    B, d = 2, 16
    q = jax.random.normal(k0, (B, S, d), dtype)
    kk = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, d), dtype)
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, d), dtype)
    got = np.asarray(swa_attention(q, kk, v, window=w, interpret=True),
                     np.float32)
    want = np.asarray(ref.flash_attention_ref(q, kk, v, window=w),
                      np.float32)
    np.testing.assert_allclose(got, want, **_tol(dtype))


def test_swa_attention_matches_flash_kernel():
    """Both kernels implement the same SWA math; the banded one simply
    never stages out-of-band k blocks."""
    from repro.kernels.swa_attention import swa_attention
    k0 = jax.random.PRNGKey(7)
    B, S, d, w = 1, 96, 32, 32
    q = jax.random.normal(k0, (B, S, d))
    kk = jax.random.normal(jax.random.fold_in(k0, 1), (B, S, d))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (B, S, d))
    banded = swa_attention(q, kk, v, window=w, interpret=True)
    flash = ops.flash_attention(q, kk, v, window=w, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(flash),
                               rtol=2e-4, atol=2e-5)
