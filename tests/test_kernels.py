"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# Pallas interpret mode on CPU takes >10 min for the full sweep — not tier-1.
pytestmark = pytest.mark.slow

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("m", [1, 7, 128, 300])
@pytest.mark.parametrize("c,k", [(4, 256), (8, 16), (16, 256)])
def test_pq_lookup_gathered(b, m, c, k):
    lut = jnp.asarray(RNG.normal(size=(b, c, k)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, k, size=(b, m, c)), jnp.int32)
    got = ops.pq_lookup_gathered(lut, codes)
    want = ref.pq_lookup_gathered_ref(lut, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [5, 512, 1000])
@pytest.mark.parametrize("c", [4, 32])
def test_pq_scan(n, c):
    k = 256
    lut = jnp.asarray(RNG.normal(size=(2, c, k)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, k, size=(n, c)), jnp.int32)
    got = ops.pq_scan(lut, codes)
    want = ref.pq_scan_ref(lut, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,w,d", [(1, 1, 8), (4, 12, 64), (2, 33, 128)])
def test_l2_dist(b, w, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, d)), dtype)
    x = jnp.asarray(RNG.normal(size=(b, w, d)), dtype)
    got = ops.l2_dist(q, x)
    want = ref.l2_dist_ref(q, x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_pq_lookup_padding_is_inert():
    """Rows padded up to the block boundary emit +INF inside the kernel —
    a fused consumer selecting over the raw block can never pick one.
    M=300 with block_m=128 leaves 84 padded lanes."""
    b, m, c, k = 2, 300, 4, 16
    lut = jnp.asarray(RNG.normal(size=(b, c, k)), jnp.float32)
    codes = jnp.asarray(RNG.integers(0, k, size=(b, m, c)), jnp.int32)
    from repro.kernels import pq_lookup as pq

    full = pq.pq_lookup_gathered(lut, codes, keep_padding=True)
    assert full.shape == (b, 384)  # padded to the 128-row block
    assert np.all(np.asarray(full[:, m:]) == np.float32(3.4e38))
    np.testing.assert_allclose(full[:, :m], ref.pq_lookup_gathered_ref(lut, codes),
                               rtol=1e-5, atol=1e-5)
    scan = pq.pq_scan(lut, jnp.asarray(RNG.integers(0, k, size=(300, c)),
                                       jnp.int32), block_n=128,
                      keep_padding=True)
    assert scan.shape == (b, 384)
    assert np.all(np.asarray(scan[:, 300:]) == np.float32(3.4e38))


def test_topk_merge_duplicate_distances_deterministic():
    """Distance ties break by ascending id — kernel and oracle must agree
    exactly (ids included), even on a batch that is mostly ties."""
    b, m, k = 3, 64, 16
    d = jnp.asarray(RNG.integers(0, 4, size=(b, m)), jnp.float32)  # heavy ties
    i = jnp.asarray(RNG.permutation(10 * m)[: b * m].reshape(b, m), jnp.int32)
    gd, gi = ops.topk_merge(d, i, k)
    wd, wi = ref.topk_merge_ref(d, i, k)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_interpret_mode_resolution():
    """Kernel wrappers run compiled on a TPU; interpret is what every
    other backend resolves to, never a silent default on a TPU."""
    from repro.kernels.backend import resolve_interpret, supports_compiled_pallas

    assert ops._interpret() == (not supports_compiled_pallas())
    assert resolve_interpret(None) == ops._interpret()
    assert supports_compiled_pallas("tpu") and not supports_compiled_pallas("gpu")
    assert not supports_compiled_pallas("cpu")
    assert resolve_interpret(False) is False  # explicit opt-out wins


@pytest.mark.parametrize("m,k", [(8, 4), (50, 10), (128, 128), (100, 200)])
def test_topk_merge(m, k):
    b = 3
    d = jnp.asarray(RNG.normal(size=(b, m)), jnp.float32)
    i = jnp.asarray(RNG.integers(0, 10_000, size=(b, m)), jnp.int32)
    gd, gi = ops.topk_merge(d, i, k)
    kk = min(k, m)  # beyond m the kernel returns INF/-1 padding
    wd, wi = ref.topk_merge_ref(d, i, kk)
    np.testing.assert_allclose(gd[:, :kk], wd, rtol=1e-6)
    # ids must agree where distances are unique (ties may reorder)
    uniq = np.diff(np.asarray(wd), axis=1) > 1e-9
    agree = np.asarray(gi)[:, 1:kk][uniq] == np.asarray(wi)[:, 1:][uniq]
    assert agree.all()
    if k > m:  # padding is inert
        assert np.all(np.asarray(gi)[:, m:] == -1)


def test_adc_matches_decoded_distance():
    """ADC with exact LUT == true squared distance to decoded vectors."""
    from repro.core import pq as pqm

    x = jnp.asarray(RNG.normal(size=(500, 32)), jnp.float32)
    codec = pqm.train_pq(x, n_chunks=8, iters=4)
    codes = pqm.encode_pq(codec, x)
    q = jnp.asarray(RNG.normal(size=(4, 32)), jnp.float32)
    lut = pqm.build_lut(codec, q)
    adc = pqm.adc_lookup_ref(lut, codes)
    decoded = pqm.decode_pq(codec, codes)
    true = ((q[:, None, :] - decoded[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(adc, true, rtol=2e-4, atol=2e-3)
