"""Per-kernel allclose vs the pure-jnp oracle, swept over shapes/dtypes
(interpret=True executes the kernel body with real BlockSpec indexing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SHAPES = [
    # B, H, KV, Sq, Sk, hd
    (1, 2, 2, 64, 64, 16),       # MHA, block-aligned
    (2, 4, 2, 75, 75, 32),       # GQA 2:1, ragged seq
    (1, 8, 1, 33, 130, 8),       # MQA, Sq != Sk
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("variant", ["causal", "full", "window",
                                     "softcap", "window+cap"])
def test_flash_attention_variants(shape, variant):
    B, H, KV, Sq, Sk, hd = shape
    q = jax.random.normal(k(1), (B, H, Sq, hd), jnp.float32)
    kk = jax.random.normal(k(2), (B, KV, Sk, hd), jnp.float32)
    v = jax.random.normal(k(3), (B, KV, Sk, hd), jnp.float32)
    kw = dict(causal=True)
    if variant == "full":
        kw = dict(causal=False)
    elif variant == "window":
        kw = dict(causal=True, window=16)
    elif variant == "softcap":
        kw = dict(causal=True, logit_cap=20.0)
    elif variant == "window+cap":
        kw = dict(causal=True, window=24, logit_cap=30.0)
    got = ops.flash_attention(q, kk, v, block_q=32, block_k=32,
                              interpret=True, **kw)
    want = ref.attention_ref(q, kk, v, **kw)
    assert_allclose(got, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, H, KV, S, hd = 1, 4, 4, 64, 32
    q = jax.random.normal(k(4), (B, H, S, hd), dtype)
    kk = jax.random.normal(k(5), (B, KV, S, hd), dtype)
    v = jax.random.normal(k(6), (B, KV, S, hd), dtype)
    got = ops.flash_attention(q, kk, v, block_q=32, block_k=32,
                              interpret=True)
    want = ref.attention_ref(q, kk, v)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    assert_allclose(got.astype(np.float32), want.astype(np.float32),
                    rtol=tol, atol=tol)
    assert got.dtype == dtype


def test_flash_attention_kv_len_mask():
    B, H, KV, S, hd = 1, 2, 2, 64, 16
    q = jax.random.normal(k(7), (B, H, S, hd), jnp.float32)
    kk = jax.random.normal(k(8), (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(k(9), (B, KV, S, hd), jnp.float32)
    got = ops.flash_attention(q, kk, v, kv_len=40, causal=False,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q[:, :, :, :], kk[:, :, :40], v[:, :, :40],
                             causal=False)
    assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_flash_matches_model_oracle():
    """The models' blockwise_attention is itself validated vs the kernel."""
    from repro.models.attention import blockwise_attention
    B, H, KV, S, hd = 2, 4, 2, 96, 16
    q = jax.random.normal(k(10), (B, S, H, hd), jnp.float32)
    kk = jax.random.normal(k(11), (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(k(12), (B, S, KV, hd), jnp.float32)
    want = blockwise_attention(q, kk, v, causal=True, q_block=32,
                               k_block=32)
    got = ops.flash_attention_bshd(q, kk, v, causal=True, block_q=32,
                                   block_k=32, interpret=True)
    assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # B, S, nh, hd, ds, chunk
    (1, 32, 2, 8, 8, 8),
    (2, 48, 4, 8, 16, 16),
    (1, 64, 4, 16, 16, 64),       # single chunk
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_scan_shapes(shape):
    B, S, nh, hd, ds, chunk = shape
    x = jax.random.normal(k(20), (B, S, nh * hd), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k(21), (B, S, nh)))
    Bm = jax.random.normal(k(22), (B, S, ds)) * 0.5
    Cm = jax.random.normal(k(23), (B, S, ds)) * 0.5
    A = -jnp.exp(jax.random.normal(k(24), (nh,)) * 0.3)
    y1, h1 = ops.ssd_scan(x, dt, Bm, Cm, A, chunk=chunk, interpret=True)
    y2, h2 = ref.ssd_scan_ref(x, dt, Bm, Cm, A, chunk=chunk)
    assert_allclose(y1, y2, rtol=3e-4, atol=3e-4)
    assert_allclose(h1, h2, rtol=3e-4, atol=3e-4)


def test_ssd_scan_state_chaining():
    """h0 continuation: two half-sequences == one full sequence."""
    B, S, nh, hd, ds, chunk = 1, 32, 2, 8, 8, 8
    x = jax.random.normal(k(25), (B, S, nh * hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k(26), (B, S, nh)))
    Bm = jax.random.normal(k(27), (B, S, ds)) * 0.5
    Cm = jax.random.normal(k(28), (B, S, ds)) * 0.5
    A = -jnp.exp(jax.random.normal(k(29), (nh,)) * 0.3)
    y_full, h_full = ops.ssd_scan(x, dt, Bm, Cm, A, chunk=chunk,
                                  interpret=True)
    y1, h1 = ops.ssd_scan(x[:, :16], dt[:, :16], Bm[:, :16], Cm[:, :16],
                          A, chunk=chunk, interpret=True)
    y2, h2 = ops.ssd_scan(x[:, 16:], dt[:, 16:], Bm[:, 16:], Cm[:, 16:],
                          A, chunk=chunk, h0=h1, interpret=True)
    assert_allclose(jnp.concatenate([y1, y2], 1), y_full, rtol=3e-4,
                    atol=3e-4)
    assert_allclose(h2, h_full, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 16, 32, 24), (3, 37, 65, 41),
                                   (1, 128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul(shape, dtype):
    E, C, d, f = shape
    x = jax.random.normal(k(30), (E, C, d), dtype)
    w = jax.random.normal(k(31), (E, d, f), dtype)
    got = ops.grouped_matmul(x, w, block_c=16, block_f=16, block_d=32,
                             interpret=True)
    want = ref.grouped_matmul_ref(x, w)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    assert_allclose(got.astype(np.float32), want.astype(np.float32),
                    rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# paper benchmark kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 1000, 4096])
def test_saxpy(n):
    x = jax.random.normal(k(40), (n,))
    y = jax.random.normal(k(41), (n,))
    assert_allclose(ops.saxpy(2.5, x, y, block=256, interpret=True),
                    ref.saxpy_ref(2.5, x, y), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hw", [(32, 32), (50, 36), (64, 128)])
def test_filter_pipeline(hw):
    H, W = hw
    img = jax.random.uniform(k(42), (H, W)) * 255
    got = ops.filter_pipeline(img, seed=3, block_rows=16,
                              interpret=True)
    want = ref.filter_pipeline_ref(img, seed=3)
    assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_filter_pipeline_is_mirrored():
    img = jnp.tile(jnp.arange(16.0)[None, :], (4, 1))
    out = ops.filter_pipeline(img, noise_scale=0.0, interpret=True)
    # column order must be reversed (values change via solarize only)
    assert float(out[0, 0]) >= float(out[0, -1])


@pytest.mark.parametrize("shape", [(8, 8, 4), (16, 24, 5), (32, 8, 3)])
def test_segmentation(shape):
    v = jax.random.uniform(k(43), shape) * 255
    got = ops.segmentation(v, interpret=True)
    want = ref.segmentation_ref(v)
    assert_allclose(got, want)
    assert set(np.unique(np.asarray(got))) <= {0.0, 128.0, 255.0}


@pytest.mark.parametrize("n", [33, 100, 256])
def test_nbody(n):
    pos = jax.random.normal(k(44), (n, 3))
    mass = jax.random.uniform(k(45), (n,)) + 0.1
    got = ops.nbody_accelerations(pos, mass, block_i=32, block_j=64,
                                  interpret=True)
    want = ref.nbody_ref(pos, mass)
    assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_nbody_energy_behaviour():
    """Loop-skeleton integration: momentum is conserved by symmetry."""
    n = 64
    pos = jax.random.normal(k(46), (n, 3))
    vel = jnp.zeros((n, 3))
    mass = jnp.ones((n,))
    p, v = pos, vel
    for _ in range(3):
        p, v = ops.nbody_step(p, v, mass, dt=1e-3, interpret=True)
    total_momentum = np.asarray((mass[:, None] * v).sum(0))
    assert np.abs(total_momentum).max() < 1e-2
