"""Property tests for the SEDAR fingerprint (hypothesis) + kernel/oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fingerprint import (fingerprints_equal, pack_tree_u32,
                                    packed_fingerprint, pytree_fingerprint,
                                    pytree_fingerprint_fused,
                                    pytree_fingerprint_lanes,
                                    tensor_fingerprint)
from repro.kernels import ops, ref


@st.composite
def small_arrays(draw):
    n = draw(st.integers(1, 400))
    dtype = draw(st.sampled_from([np.float32, np.float16]))
    seed = draw(st.integers(0, 2**31 - 1))
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(n).astype(dtype))


@settings(max_examples=25, deadline=None)
@given(small_arrays())
def test_fingerprint_deterministic(x):
    a = np.asarray(tensor_fingerprint(x))
    b = np.asarray(tensor_fingerprint(x))
    assert np.array_equal(a, b)


@settings(max_examples=25, deadline=None)
@given(small_arrays(), st.integers(0, 10**6), st.integers(0, 31))
def test_single_bitflip_detected(x, idx, bit):
    """Any single flipped bit changes the hash (SEDAR's detection premise)."""
    from repro.core.injection import flip_bit
    idx = idx % x.size
    bit = bit % (16 if x.dtype == jnp.float16 else 32)
    if x.dtype == jnp.float16:
        x = x.astype(jnp.float32)
    y = flip_bit(x, idx, bit)
    fa = np.asarray(tensor_fingerprint(x))
    fb = np.asarray(tensor_fingerprint(y))
    assert not np.array_equal(fa[:2], fb[:2])


@settings(max_examples=20, deadline=None)
@given(st.integers(4, 200), st.integers(0, 2**31 - 1))
def test_permutation_sensitive(n, seed):
    """Order sensitivity: swapping two distinct elements changes h1."""
    rs = np.random.RandomState(seed)
    x = np.arange(1, n + 1, dtype=np.float32) + rs.rand(n).astype(np.float32)
    y = x.copy()
    y[0], y[n - 1] = y[n - 1], y[0]
    fa = np.asarray(tensor_fingerprint(jnp.asarray(x)))
    fb = np.asarray(tensor_fingerprint(jnp.asarray(y)))
    assert not np.array_equal(fa[:2], fb[:2])


def test_pytree_fingerprint_structure():
    tree = {"a": jnp.ones((3, 4)), "b": {"c": jnp.zeros((7,))}}
    fp = pytree_fingerprint(tree)
    assert fp.shape == (2, 4) and fp.dtype == jnp.uint32
    assert bool(fingerprints_equal(fp, fp))


def test_mismatch_report_localizes_leaf():
    from repro.core.fingerprint import mismatch_report
    t1 = {"a": jnp.ones((8,)), "b": jnp.zeros((8,))}
    t2 = {"a": jnp.ones((8,)), "b": jnp.zeros((8,)).at[3].set(1e-9)}
    fp1, fp2 = pytree_fingerprint(t1), pytree_fingerprint(t2)
    rep = mismatch_report(t1, fp1, fp2)
    assert len(rep) == 1 and "b" in rep[0]["leaf"]


@pytest.mark.parametrize("shape", [(5,), (128,), (1000,), (8, 129), (3, 5, 7)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_kernel_matches_oracle(shape, dtype):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(*shape).astype(dtype))
    a = np.asarray(ops.fingerprint(x, block_rows=8))
    b = np.asarray(ref.fingerprint_ref(x))
    assert np.array_equal(a[:2], b[:2])          # hashes bit-exact
    sa = np.frombuffer(np.asarray(a[2]).tobytes(), np.float32)[0]
    sb = np.frombuffer(np.asarray(b[2]).tobytes(), np.float32)[0]
    assert abs(sa - sb) <= 1e-3 * max(abs(sb), 1)  # sum: fp-order tolerance


def test_kernel_block_size_invariance():
    x = jnp.asarray(np.random.RandomState(1).randn(3000).astype(np.float32))
    a = np.asarray(ops.fingerprint(x, block_rows=8))[:2]
    b = np.asarray(ops.fingerprint(x, block_rows=16))[:2]
    assert np.array_equal(a, b)


def _mixed_tree():
    rs = np.random.RandomState(2)
    return {"a": jnp.asarray(rs.randn(5).astype(np.float32)),
            "b": jnp.asarray(rs.randn(40, 128).astype(np.float32)),
            "c": jnp.asarray(rs.randn(3, 12, 256).astype(np.float32)),
            "d": jnp.asarray(rs.randn(37, 2688).astype(np.float32)),
            "e": jnp.asarray(rs.randn(6, 7).astype(np.float32)
                             ).astype(jnp.bfloat16),
            "f": jnp.arange(7, dtype=jnp.int32),
            "g": jnp.zeros((0,), jnp.float32),
            "h": jnp.float32(3.5)}


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_fingerprint_equals_packed_reference(use_pallas):
    """The in-place fused hash equals the hash of the packed copy."""
    tree = _mixed_tree()
    got = np.asarray(pytree_fingerprint_fused(tree, use_pallas=use_pallas))
    want = np.asarray(packed_fingerprint(pack_tree_u32(tree)))
    np.testing.assert_array_equal(got[:2], want[:2])


@pytest.mark.parametrize("n_lanes", [1, 2, 3, 7, 64])
def test_lane_fingerprints_equal_padded_packed_reference(n_lanes):
    """Lane i is the hash of words [i*W, (i+1)*W) of the zero-padded
    packed state, computed without packing it."""
    tree = _mixed_tree()
    u = pack_tree_u32(tree)
    width = -(-u.shape[0] // n_lanes)
    u = jnp.pad(u, (0, n_lanes * width - u.shape[0]))
    want = np.asarray(jax.vmap(packed_fingerprint)(u.reshape(n_lanes, width)))
    got = np.asarray(pytree_fingerprint_lanes(tree, n_lanes))
    np.testing.assert_array_equal(got[:, :2], want[:, :2])
