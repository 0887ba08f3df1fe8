"""Property tests for the fixed-point quantization layer (paper §III)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quant import (
    fake_quant, pack_int4, pack_weights, packable, packed_pad_ok, qmax,
    quant_linear_ref, quantize, unpack_int4, unpack_weights,
)

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def arrays(draw, shape):
    data = draw(st.lists(
        st.floats(-100, 100, allow_nan=False, width=32),
        min_size=int(np.prod(shape)), max_size=int(np.prod(shape))))
    return np.asarray(data, np.float32).reshape(shape)


@st.composite
def matrix(draw):
    k = draw(st.integers(2, 24))
    n = draw(st.integers(2, 24))
    return arrays(draw, (k, n))


@given(matrix(), st.sampled_from([4, 6, 8]))
def test_roundtrip_error_bound(w, wl):
    """|dequant(quant(x)) - x| <= scale/2 elementwise."""
    q = quantize(jnp.asarray(w), wl, axis=0)
    err = np.abs(np.asarray(q.dequant()) - w)
    bound = np.asarray(q.scale) / 2 + 1e-6
    assert (err <= bound + 1e-4 * np.abs(w)).all()


@given(matrix(), st.sampled_from([4, 6, 8]))
def test_codes_in_range(w, wl):
    q = quantize(jnp.asarray(w), wl, axis=0)
    m = qmax(wl)
    assert int(jnp.max(jnp.abs(q.values.astype(jnp.int32)))) <= m


@given(matrix())
def test_idempotent(w):
    """fake_quant(fake_quant(x)) == fake_quant(x)."""
    a = fake_quant(jnp.asarray(w), 6, axis=0)
    b = fake_quant(a, 6, axis=0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@given(matrix(), st.sampled_from([4, 6, 8]))
def test_monotone_in_bits(w, wl):
    """More bits never increases the Frobenius reconstruction error."""
    wj = jnp.asarray(w)
    errs = [float(jnp.linalg.norm(wj - quantize(wj, b, 0).dequant()))
            for b in (4, 6, 8)]
    assert errs[0] >= errs[1] >= errs[2] - 1e-5


def test_error_decreases_with_bits_realistic():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (128, 128))
    errs = {b: float(jnp.linalg.norm(w - quantize(w, b, 0).dequant())
                     / jnp.linalg.norm(w)) for b in (4, 6, 8)}
    assert errs[4] > 2 * errs[6] > 3 * errs[8]


def test_quant_linear_ref_shapes():
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (5, 16))
    w = jax.random.normal(key, (16, 8))
    y = quant_linear_ref(x, w, 8, 8)
    assert y.shape == (5, 8)
    rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    assert rel < 0.05


@given(st.integers(1, 12), st.integers(1, 12), st.integers(0, 2**31 - 1))
def test_pack_unpack_int4(r, c, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(-8, 8, size=(r, 2 * c)).astype(np.int8)
    packed = pack_int4(jnp.asarray(codes))
    assert packed.shape == (r, c)
    assert packed.dtype == jnp.int8
    out = unpack_int4(packed)
    np.testing.assert_array_equal(np.asarray(out), codes)


def test_pack_unpack_int4_exhaustive_range():
    """Every one of the 256 (lo, hi) nibble pairs round-trips exactly —
    the full int4 code range [-8, 7] in both byte halves."""
    lo, hi = np.meshgrid(np.arange(-8, 8), np.arange(-8, 8))
    codes = np.stack([lo.ravel(), hi.ravel()], axis=-1).astype(np.int8)
    out = unpack_int4(pack_int4(jnp.asarray(codes)))
    np.testing.assert_array_equal(np.asarray(out), codes)


@pytest.mark.parametrize("n", [256, 512, 768, 640])
def test_pack_int4_tile_layout(n):
    """The packed byte order the kernels read: within each 256-column
    tile, byte j holds column j in its low nibble and column j + 128 in
    its high nibble, so a kernel unpacks 128-byte half-tiles onto
    128-lane boundaries. A narrower last tile (640 = 2 tiles + 128)
    splits at its own half width. Round-trips exactly either way."""
    rng = np.random.default_rng(n)
    codes = rng.integers(-8, 8, size=(3, n)).astype(np.int8)
    packed = np.asarray(pack_int4(jnp.asarray(codes))).astype(np.uint8)
    assert packed.shape == (3, n // 2)
    lo, hi = packed & 0x0F, packed >> 4
    want_lo, want_hi = [], []
    for t0 in range(0, n, 256):
        half = min(256, n - t0) // 2
        want_lo.append(codes[:, t0:t0 + half])
        want_hi.append(codes[:, t0 + half:t0 + 2 * half])
    np.testing.assert_array_equal(lo, np.concatenate(want_lo, 1) & 0x0F)
    np.testing.assert_array_equal(hi, np.concatenate(want_hi, 1) & 0x0F)
    np.testing.assert_array_equal(np.asarray(unpack_int4(jnp.asarray(
        packed.astype(np.int8)))), codes)


def test_pack_int4_rejects_odd_last_dim():
    with pytest.raises(ValueError, match="even last dim"):
        pack_int4(jnp.zeros((4, 5), jnp.int8))


@given(matrix())
def test_pack_weights_refuses_small_axes(w):
    """The 2..24-wide hypothesis axes are narrower than one pack tile
    (the kernels read whole 256-column tiles), so pack_weights must
    refuse every one of them."""
    q = quantize(jnp.asarray(w), 4, axis=0)
    assert not packed_pad_ok(w.shape[-1])
    assert not packable(q) and pack_weights(q) is q


@pytest.mark.parametrize("n", [256, 512, 768])
def test_pack_weights_roundtrip(n):
    """pack_weights/unpack_weights is exact and dequant-invariant on any
    W4 tensor whose last dim is whole pack tiles; other dims and W6/W8
    stay carriers."""
    w = jnp.asarray(np.random.default_rng(n).normal(size=(16, n)),
                    jnp.float32)
    q = quantize(w, 4, axis=0)
    assert packed_pad_ok(n)
    p = pack_weights(q)
    assert p.packed and p.shape == q.shape
    assert p.values.shape[-1] == n // 2
    back = unpack_weights(p)
    np.testing.assert_array_equal(np.asarray(back.values),
                                  np.asarray(q.values))
    np.testing.assert_array_equal(np.asarray(p.dequant()),
                                  np.asarray(q.dequant()))


def test_storage_bits_accounting():
    """storage_bits reports RESIDENT bytes: an unpacked W4 tensor still
    occupies a full int8 carrier (8 bits/code); packing halves it to the
    true 4; W6 has no byte-aligned packing and a pad-inflating W4 axis
    refuses to pack — both stay at an honest 8."""
    w = jnp.ones((64, 256))
    q = quantize(w, 4, axis=0)
    assert q.storage_bits() == 64 * 256 * 8 + 32 * 256
    p = pack_weights(q)
    assert p.packed and p.values.shape == (64, 128)
    assert p.shape == (64, 256)
    assert p.storage_bits() == 64 * 256 * 4 + 32 * 256
    q6 = quantize(w, 6, axis=0)
    assert pack_weights(q6) is q6          # carrier-resident, honest 8 bits
    assert q6.storage_bits() == 64 * 256 * 8 + 32 * 256
    q32 = quantize(jnp.ones((64, 32)), 4, axis=0)
    assert pack_weights(q32) is q32        # pad-inflating axis: carrier
    assert q32.storage_bits() == 64 * 32 * 8 + 32 * 32
