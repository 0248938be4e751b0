"""Packed bitvector ops: roundtrip, reductions, popcount, jnp parity."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import bitvector as bv


@given(st.lists(st.booleans(), min_size=0, max_size=300))
@settings(max_examples=100, deadline=None)
def test_pack_unpack_roundtrip(bits):
    arr = np.array(bits, dtype=bool)
    words = bv.pack(arr)
    assert words.dtype == np.uint32
    out = bv.unpack(words, len(bits))
    assert np.array_equal(out, arr)


@given(st.integers(1, 5), st.integers(1, 200), st.integers(0, 2**31))
@settings(max_examples=60, deadline=None)
def test_reductions_match_unpacked(p, r, seed):
    rng = np.random.default_rng(seed)
    bits = rng.random((p, r)) < 0.4
    words = bv.pack(bits)
    assert np.array_equal(bv.unpack(bv.bv_and_many(words), r), bits.all(axis=0))
    assert np.array_equal(bv.unpack(bv.bv_or_many(words), r), bits.any(axis=0))
    assert bv.popcount(bv.pack(bits[0])) == int(bits[0].sum())
    idx = bv.select_indices(bv.pack(bits[0]), r)
    assert np.array_equal(idx, np.nonzero(bits[0])[0])


def test_jnp_parity():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    bits = rng.random((3, 130)) < 0.5
    words = bv.pack(bits)
    jwords = bv.jnp_pack(jnp.asarray(bits))
    assert np.array_equal(np.asarray(jwords), words)
    assert np.array_equal(
        np.asarray(bv.jnp_unpack(jnp.asarray(words), 130)), bits
    )
    assert int(bv.jnp_popcount(jnp.asarray(words))) == int(bits.sum())
    assert np.array_equal(
        np.asarray(bv.jnp_and_many(jnp.asarray(words))), bv.bv_and_many(words)
    )


@given(st.integers(0, 2**31), st.integers(0, 400))
@settings(max_examples=60, deadline=None)
def test_popcount_fallback_matches(seed, r):
    """``np.bitwise_count`` popcounts agree with the unpacked ground truth
    on arbitrary shapes (including empty and non-contiguous inputs)."""
    rng = np.random.default_rng(seed)
    bits = rng.random(r) < 0.3
    words = bv.pack(bits)
    expected = int(bits.sum())
    assert bv.popcount(words) == expected
    # non-contiguous view: popcount must not assume contiguity
    two = np.stack([words, words])
    assert bv.popcount(two.T) == 2 * expected
    assert bv.popcount_rows(two).tolist() == [expected, expected]
