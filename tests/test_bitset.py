import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st  # noqa: E402

from repro.core import bitset  # noqa: E402


@given(st.integers(0, 2**32 - 1), st.integers(1, 200))
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip(seed, n):
    rng = np.random.default_rng(seed)
    bits = rng.random(n) < 0.3
    packed = bitset.np_pack(bits)
    assert packed.shape == (bitset.n_words(n),)
    np.testing.assert_array_equal(bitset.np_unpack(packed, n), bits)
    # jnp path agrees with numpy path
    jpacked = np.asarray(bitset.pack(jnp.asarray(bits)))
    np.testing.assert_array_equal(jpacked, packed)
    np.testing.assert_array_equal(
        np.asarray(bitset.unpack(jnp.asarray(packed), n)), bits)


@given(st.integers(0, 2**32 - 1), st.integers(1, 300))
@settings(max_examples=30, deadline=None)
def test_popcount(seed, n):
    rng = np.random.default_rng(seed)
    bits = rng.random(n) < 0.5
    packed = bitset.np_pack(bits)
    assert bitset.np_popcount(packed) == bits.sum()
    assert int(bitset.popcount(jnp.asarray(packed))) == bits.sum()


def test_from_indices_and_to_indices():
    idx = np.array([0, 3, 31, 32, 64, 64, 90])  # duplicate on purpose
    out = np.asarray(bitset.from_indices(jnp.asarray(idx), 96))
    expected = bitset.np_from_indices(idx, 96)
    np.testing.assert_array_equal(out, expected)
    np.testing.assert_array_equal(
        bitset.np_to_indices(expected, 96), np.unique(idx))


def _row(density: str, n_bits: int, seed: int) -> np.ndarray:
    """Words of `n_bits` bits laid out by `density`, with stray bits set
    past `n_bits` in the last word wherever it has room for them."""
    w = bitset.n_words(n_bits)
    rng = np.random.default_rng(seed)
    words = np.zeros(w, np.uint32)
    if density == "bit_0":
        words[0] = 1
    elif density == "bit_last":
        words[-1] = np.uint32(1) << np.uint32((n_bits - 1) % 32)
    elif density == "sparse":
        words[rng.choice(w, max(1, w // 50), replace=False)] = rng.integers(
            1, 2**32, max(1, w // 50), dtype=np.uint64).astype(np.uint32)
    elif density == "every_other":
        words[::2] = rng.integers(1, 2**32, len(words[::2]), dtype=np.uint64
                                  ).astype(np.uint32)
    elif density == "ones":
        words[:] = 0xFFFFFFFF
    elif density.startswith("units_"):
        # k nonzero units (uint64 pairs for an even word count, words for
        # an odd one): the two sides of the whole-row unpack's switch
        pair = 2 if w % 2 == 0 else 1
        units = w // pair
        k = units // 2 + int(density[len("units_"):])
        # the last unit is one of them: the stray bits below land there
        chosen = rng.choice(units - 1, k - 1, replace=False)
        for u in np.r_[chosen, units - 1]:
            words[u * pair + rng.integers(pair)] = rng.integers(
                1, 2**32, dtype=np.uint64)
    else:
        assert density == "empty"
    if n_bits % 32 and density != "empty":
        words[-1] |= np.uint32(0xFFFFFFFF) << np.uint32(n_bits % 32)
    return words


@pytest.mark.parametrize("n_bits", [96, 95, 127, 128, 2**16 + 5, 2**16])
@pytest.mark.parametrize("density", ["empty", "bit_0", "bit_last", "sparse",
                                     "every_other", "ones", "units_0",
                                     "units_1"])
def test_np_to_indices_matches_full_unpack(density, n_bits):
    words = _row(density, n_bits, seed=n_bits)
    expected = np.nonzero(bitset.np_unpack(words, n_bits))[-1]
    got = bitset.np_to_indices(words, n_bits)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, expected)
    assert (np.diff(got) > 0).all()
    ids, nz = bitset.np_indices_nz(words, n_bits)
    np.testing.assert_array_equal(ids, expected)
    assert nz == np.count_nonzero(words)
    # a stack of rows reads row after row, as np.nonzero does
    stack = np.stack([words, words[::-1]])
    np.testing.assert_array_equal(bitset.np_to_indices(stack, n_bits),
                                  np.nonzero(bitset.np_unpack(stack,
                                                              n_bits))[-1])


def test_from_indices_with_validity_mask():
    idx = jnp.asarray([5, 17, 40, 0, 0])
    valid = jnp.asarray([True, True, True, False, False])
    out = np.asarray(bitset.from_indices(idx, 64, valid=valid))
    expected = bitset.np_from_indices(np.array([5, 17, 40]), 64)
    np.testing.assert_array_equal(out, expected)


def test_count_and_not():
    rng = np.random.default_rng(0)
    a = rng.random((8, 130)) < 0.4
    m = rng.random(130) < 0.5
    got = np.asarray(bitset.count_and_not(
        jnp.asarray(bitset.np_pack(a)), jnp.asarray(bitset.np_pack(m))))
    np.testing.assert_array_equal(got, (a & ~m).sum(axis=1))


def test_is_subset():
    a = bitset.np_pack(np.array([1, 0, 1, 0, 0, 0], bool))
    b = bitset.np_pack(np.array([1, 1, 1, 0, 1, 0], bool))
    assert bool(bitset.is_subset(jnp.asarray(a), jnp.asarray(b)))
    assert not bool(bitset.is_subset(jnp.asarray(b), jnp.asarray(a)))


@pytest.mark.parametrize("n", [1, 31, 32, 33, 64, 100])
def test_or_rows(n):
    rng = np.random.default_rng(n)
    rows = rng.random((5, n)) < 0.3
    packed = jnp.asarray(bitset.np_pack(rows))
    got = np.asarray(bitset.or_rows(packed, axis=0))
    np.testing.assert_array_equal(got, bitset.np_pack(rows.any(axis=0)))
