"""Per-kernel shape/dtype sweeps: Pallas vs pure-jnp oracle, plus
oracle-vs-explicit-recurrence cross-checks. The metadata kernels (phash)
run in the mode ``repro.kernels.mode`` derives from the backend — the
Pallas interpreter under ``JAX_PLATFORMS=cpu``; the model-stack kernels
default to the interpreter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return ATOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),       # MHA
    (2, 256, 8, 2, 64, 128, 64),      # GQA, rectangular blocks
    (1, 512, 4, 1, 32, 128, 128),     # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(B, S, H, KV, hd, bq, bk, dtype, window):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention_fwd(q, k, v, causal=True, window=window,
                              block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=1e-2)


def test_flash_attention_grad_matches_ref():
    from repro.kernels.flash_attention import ops
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 16))
    k = jax.random.normal(ks[1], (1, 128, 2, 16))
    v = jax.random.normal(ks[2], (1, 128, 2, 16))
    g1 = jax.grad(lambda q_: ops.flash_attention(q_, k, v).sum())(q)
    g2 = jax.grad(lambda q_: attention_ref(q_, k, v).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba2 SSD
# ---------------------------------------------------------------------------

def _ssd_explicit(x, dt, A, Bc, Cc):
    """Explicit per-timestep recurrence (ground truth)."""
    B, S, H, hd = x.shape
    N = Bc.shape[-1]
    h = jnp.zeros((B, H, hd, N))
    ys = []
    for t in range(S):
        a = jnp.exp(dt[:, t] * A[None])                       # [B,H]
        h = h * a[..., None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", x[:, t], Bc[:, t], dt[:, t])
        ys.append(jnp.einsum("bn,bhpn->bhp", Cc[:, t], h))
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("B,S,H,hd,N,chunk", [
    (1, 64, 2, 8, 4, 16), (2, 128, 3, 16, 8, 32), (1, 96, 1, 8, 16, 32),
])
def test_ssd_chunked_matches_explicit(B, S, H, hd, N, chunk):
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    y1, h1 = ssd_chunked(x, dt, A, Bc, Cc, chunk=chunk)
    y2, h2 = _ssd_explicit(x, dt, A, Bc, Cc)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd,N", [(2, 128, 3, 16, 8), (1, 64, 2, 8, 4)])
def test_ssd_kernel_vs_ref(B, S, H, hd, N, dtype):
    from repro.kernels.mamba2_ssd.kernel import ssd_fwd
    from repro.kernels.mamba2_ssd.ref import ssd_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(
        jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, N), dtype)
    Cc = jax.random.normal(ks[4], (B, S, N), dtype)
    y1, h1 = ssd_fwd(x, dt, A, Bc, Cc, chunk=32)
    y2, h2 = ssd_ref(x, dt, A, Bc, Cc, chunk=32)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=_tol(dtype) * 4, rtol=2e-2)
    np.testing.assert_allclose(h1, h2, atol=_tol(dtype) * 4, rtol=2e-2)


def test_ssd_decode_step_matches_scan():
    from repro.models.mamba2 import ssd_chunked, ssd_decode_step
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, H, hd, N = 1, 17, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    h = jnp.zeros((B, H, hd, N))
    ys = []
    for t in range(S):
        y, h = ssd_decode_step(x[:, t:t+1], dt[:, t:t+1], A,
                               Bc[:, t:t+1], Cc[:, t:t+1], h)
        ys.append(y[:, 0])
    y_dec = jnp.stack(ys, 1)
    y_ref, h_ref = ssd_chunked(x, dt, A, Bc, Cc, chunk=17)
    np.testing.assert_allclose(y_dec, y_ref, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(h, h_ref, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# rwkv6 WKV
# ---------------------------------------------------------------------------

def _wkv_explicit(r, k, v, w, u):
    B, S, H, hd = r.shape
    s = jnp.zeros((B, H, hd, hd))
    ys = []
    for t in range(S):
        rt, kt, vt, wt = r[:, t], k[:, t], v[:, t], w[:, t]
        y = jnp.einsum("bhc,bhcd->bhd", rt, s) + \
            jnp.einsum("bhc,bhc,bhd->bhd", rt * u[None], kt, vt)
        s = s * wt[..., None] + jnp.einsum("bhc,bhd->bhcd", kt, vt)
        ys.append(y)
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 64, 2, 8, 16), (2, 96, 1, 16, 32),
])
def test_wkv_chunked_matches_explicit(B, S, H, hd, chunk):
    from repro.models.rwkv6 import wkv6_chunked
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, hd))))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    y1, s1 = wkv6_chunked(r, k, v, w, u, chunk=chunk)
    y2, s2 = _wkv_explicit(r, k, v, w, u)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-3)


def test_wkv_strong_decay_is_finite():
    """Regression: data-dependent decay can underflow w to 0 in f32; the
    chunked form must stay finite (masked-exponent computation)."""
    from repro.models.rwkv6 import wkv6_chunked
    B, S, H, hd = 1, 64, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jnp.full((B, S, H, hd), 1e-45)            # flushed-to-zero decay
    u = jnp.ones((H, hd))
    y, s = wkv6_chunked(r, k, v, w, u, chunk=16)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv_kernel_vs_ref(dtype):
    from repro.kernels.rwkv6_scan.kernel import wkv6_fwd
    from repro.kernels.rwkv6_scan.ref import wkv6_ref
    B, S, H, hd = 2, 128, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    w = jnp.exp(-jnp.exp(jax.random.normal(
        ks[3], (B, S, H, hd)) * 0.5)).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, hd)) * 0.1).astype(jnp.float32)
    y1, s1 = wkv6_fwd(r, k, v, w, u, chunk=32)
    y2, s2 = wkv6_ref(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=_tol(dtype) * 4, rtol=2e-2)
    np.testing.assert_allclose(s1, s2, atol=_tol(dtype) * 4, rtol=2e-2)


def test_wkv_decode_step_matches_scan():
    from repro.models.rwkv6 import wkv6_chunked, wkv6_step
    B, S, H, hd = 1, 32, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, hd)) * 0.3))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s = jnp.zeros((B, H, hd, hd))
    ys = []
    for t in range(S):
        y, s = wkv6_step(r[:, t:t+1], k[:, t:t+1], v[:, t:t+1],
                         w[:, t:t+1], u, s)
        ys.append(y[:, 0])
    y_ref, s_ref = wkv6_chunked(r, k, v, w, u, chunk=8)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref, atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(s, s_ref, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# grouped matmul + phash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,D,F,bc,bf,bd", [
    (4, 64, 32, 48, 32, 16, 16), (2, 128, 64, 64, 64, 64, 32),
    (8, 32, 16, 16, 32, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_sweep(E, C, D, F, bc, bf, bd, dtype):
    from repro.kernels.moe_gmm.kernel import gmm
    from repro.kernels.moe_gmm.ref import gmm_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    out = gmm(x, w, block_c=bc, block_f=bf, block_d=bd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(gmm_ref(x, w), np.float32),
                               atol=_tol(dtype) * D ** 0.5, rtol=2e-2)


def test_phash_chain_kernel_matches_ref_and_store():
    """The fused chain variant: component partitions, hint partitions and
    chain signatures agree with the numpy oracle, and both agree with the
    scalar store hash on partition placement."""
    from repro.core.store import _hash_key
    from repro.kernels.phash.ops import phash_chains
    from repro.kernels.phash.ref import phash_chain_ref
    rng = np.random.default_rng(3)
    n, d = 21, 7
    par = rng.integers(0, 2**31, (n, d))
    nam = rng.integers(0, 2**32, (n, d))
    hin = rng.integers(0, 2**31, n)
    dep = rng.integers(1, d + 1, n)
    comp, hint_parts, sigs = phash_chains(par, nam, hin, dep, 64)
    rcomp, rhint, rsig = phash_chain_ref(par, nam, hin, dep, 64)
    assert (comp == rcomp).all()
    assert (hint_parts == rhint).all()
    assert (sigs == rsig).all()
    assert all(hint_parts[i] == _hash_key(int(hin[i])) % 64
               for i in range(n))
    # identical chains hash identically; differing names do not
    c2, h2, s2 = phash_chains(par, nam, hin, dep, 64)
    assert (s2 == sigs).all()
    _c3, _h3, s3 = phash_chains(par, (nam + 1) & 0xFFFFFFFF, hin, dep, 64)
    assert (s3 != sigs).any()


def test_phash_kernel_matches_ref():
    from repro.kernels import mode
    from repro.kernels.phash.kernel import phash
    from repro.kernels.phash.ref import phash_ref
    keys = jnp.asarray((np.arange(8192, dtype=np.uint64) * 2654435761
                        % (2**31)).astype(np.int32))
    out = phash(keys, n_partitions=128, block_n=512,
                interpret=mode.interpret())
    assert (np.asarray(out) == phash_ref(keys, 128)).all()
