"""K-ary sum tree invariants: exact prefix-sum semantics, batched update
semantics (last-writer-wins), sampling distribution — incl. hypothesis
property tests over capacities/fanouts/priorities."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import sumtree


def build_ref(capacity, seed=0, low=0.0, high=2.0):
    rng = np.random.default_rng(seed)
    pri = rng.uniform(low, high, capacity).astype(np.float32)
    return pri


@pytest.mark.parametrize("capacity,fanout", [
    (1, 2), (5, 4), (100, 8), (1000, 128), (4096, 128), (4097, 64),
    (65536, 256), (999, 2),
])
def test_build_invariant_and_total(capacity, fanout):
    spec = sumtree.make_spec(capacity, fanout)
    pri = build_ref(capacity)
    tree = sumtree.build(spec, jnp.asarray(pri))
    assert sumtree.check_invariant(spec, tree)
    np.testing.assert_allclose(float(tree[0]), pri.sum(), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(sumtree.leaves(spec, tree)), pri, rtol=1e-6)


def test_levels_are_fanout_aligned():
    spec = sumtree.make_spec(1000, 128)
    assert all(s % spec.fanout == 0 for s in spec.level_sizes)
    assert spec.level_sizes[0] == spec.fanout          # padded root (paper)
    # space complexity Θ(N + (N-1)/(K-1)) + padded root/top groups — §IV-C5
    assert spec.total_size <= 1000 + 999 // 127 + 3 * 128 + 2


def test_update_sequential_semantics_with_duplicates():
    spec = sumtree.make_spec(50, 4)
    pri = build_ref(50, seed=1)
    tree = sumtree.build(spec, jnp.asarray(pri))
    idx = jnp.array([7, 3, 7, 7, 12, 3], jnp.int32)
    val = jnp.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0], jnp.float32)
    tree2 = sumtree.update(spec, tree, idx, val)
    ref = pri.copy()
    for i, v in zip(np.asarray(idx), np.asarray(val)):
        ref[i] = v
    np.testing.assert_allclose(np.asarray(sumtree.leaves(spec, tree2)), ref,
                               rtol=1e-5)
    assert sumtree.check_invariant(spec, tree2)


def test_sample_matches_inverse_cdf_exactly():
    spec = sumtree.make_spec(777, 16)
    pri = build_ref(777, seed=2, low=0.01)
    tree = sumtree.build(spec, jnp.asarray(pri))
    rng = np.random.default_rng(3)
    u = rng.uniform(0, 1, 2048).astype(np.float32)
    leaf, p = sumtree.sample(spec, tree, jnp.asarray(u))
    cdf = np.cumsum(pri)
    expect = np.searchsorted(cdf, u * float(tree[0]), side="left")
    expect = np.minimum(expect, 776)
    match = (np.asarray(leaf) == expect).mean()
    assert match > 0.999  # fp ties only
    np.testing.assert_allclose(np.asarray(p), pri[np.asarray(leaf)], rtol=1e-5)


def test_zero_priority_never_sampled():
    """The lazy-writing invariant (paper §IV-D2): priority-0 slots are
    invisible to sampling."""
    spec = sumtree.make_spec(256, 8)
    pri = build_ref(256, seed=4, low=0.5)
    zero_at = np.array([0, 17, 100, 255])
    pri[zero_at] = 0.0
    tree = sumtree.build(spec, jnp.asarray(pri))
    u = jnp.asarray(np.random.default_rng(5).uniform(0, 1, 4096).astype(np.float32))
    leaf, _ = sumtree.sample(spec, tree, u)
    assert not np.isin(np.asarray(leaf), zero_at).any()


def test_sampling_distribution_chi_square():
    spec = sumtree.make_spec(16, 4)
    pri = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 1, 2, 3, 4, 5, 6, 7, 8],
                     np.float32)
    tree = sumtree.build(spec, jnp.asarray(pri))
    n = 40000
    u = jax.random.uniform(jax.random.PRNGKey(0), (n,))
    leaf, _ = sumtree.sample(spec, tree, u)
    counts = np.bincount(np.asarray(leaf), minlength=16)
    expected = pri / pri.sum() * n
    chi2 = ((counts - expected) ** 2 / expected).sum()
    assert chi2 < 50  # df=15; 50 is far beyond the 0.999 quantile (~37.7)


@settings(max_examples=30, deadline=None)
@given(
    capacity=st.integers(1, 300),
    fanout=st.sampled_from([2, 3, 4, 8, 16, 128]),
    seed=st.integers(0, 10_000),
)
def test_property_update_then_invariant(capacity, fanout, seed):
    spec = sumtree.make_spec(capacity, fanout)
    rng = np.random.default_rng(seed)
    pri = rng.uniform(0, 3, capacity).astype(np.float32)
    tree = sumtree.build(spec, jnp.asarray(pri))
    b = rng.integers(1, 20)
    idx = rng.integers(0, capacity, b).astype(np.int32)
    val = rng.uniform(0, 5, b).astype(np.float32)
    tree = sumtree.update(spec, tree, jnp.asarray(idx), jnp.asarray(val))
    assert sumtree.check_invariant(spec, tree)
    ref = pri.copy()
    for i, v in zip(idx, val):
        ref[i] = v
    np.testing.assert_allclose(np.asarray(sumtree.leaves(spec, tree)), ref,
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    capacity=st.integers(2, 200),
    fanout=st.sampled_from([2, 4, 8, 64]),
    seed=st.integers(0, 10_000),
)
def test_property_sample_in_range_and_positive(capacity, fanout, seed):
    spec = sumtree.make_spec(capacity, fanout)
    rng = np.random.default_rng(seed)
    pri = rng.uniform(0.1, 3, capacity).astype(np.float32)
    tree = sumtree.build(spec, jnp.asarray(pri))
    u = jnp.asarray(rng.uniform(0, 1, 64).astype(np.float32))
    leaf, p = sumtree.sample(spec, tree, u)
    assert (np.asarray(leaf) >= 0).all() and (np.asarray(leaf) < capacity).all()
    assert (np.asarray(p) > 0).all()


def test_add_accumulates_duplicates():
    spec = sumtree.make_spec(64, 8)
    tree = sumtree.build(spec, jnp.zeros(64))
    idx = jnp.array([5, 5, 5, 9], jnp.int32)
    tree = sumtree.add(spec, tree, idx, jnp.ones(4))
    leaves = np.asarray(sumtree.leaves(spec, tree))
    assert leaves[5] == 3.0 and leaves[9] == 1.0
    assert float(tree[0]) == 4.0


def _sample_flat_slices(spec, tree, u):
    """The descent as it read sibling rows before the row gather: one 1-D
    ``dynamic_slice`` of the flat tree per draw under ``vmap`` (a serial
    loop over the draws on TPU).  Kept as the reference for the rows."""
    u = jnp.asarray(u, tree.dtype)
    residual = jnp.clip(u, 1e-12, 1.0 - 1e-7) * tree[0]
    group = jnp.zeros(u.shape, jnp.int32)  # start: children of root = group 0
    k = spec.fanout

    for level in range(1, spec.leaf_level + 1):
        base = spec.offsets[level] + group * k

        def read_row(b):
            return jax.lax.dynamic_slice(tree, (b,), (k,))

        rows = jax.vmap(read_row)(base)            # (B, K) sibling rows
        csum = jnp.cumsum(rows, axis=-1)           # lane-parallel scan
        hit = csum >= residual[:, None]
        cutoff = jnp.argmax(hit, axis=-1).astype(jnp.int32)
        # No-hit (fp rounding at the tail): clamp to last child.
        cutoff = jnp.where(jnp.any(hit, axis=-1), cutoff, k - 1)
        picked = jnp.take_along_axis(csum, cutoff[:, None], axis=-1)[:, 0]
        row_val = jnp.take_along_axis(rows, cutoff[:, None], axis=-1)[:, 0]
        residual = residual - (picked - row_val)   # subtract prefix before cutoff
        group = group * k + cutoff

    leaf = jnp.minimum(group, spec.capacity - 1)
    return leaf, tree[spec.leaf_offset + leaf]


@pytest.mark.parametrize("root_scale", [1.0, 1.01], ids=["exact", "stale_root"])
@pytest.mark.parametrize("capacity,fanout", [
    (1000, 8), (16389, 128), (70000, 128), (2 ** 20, 128),
])
def test_row_gather_descent_matches_flat_slices(capacity, fanout, root_scale):
    """The row-gather descent returns exactly the leaves and priorities
    of the per-draw flat-slice descent, tail draws included.  A root
    above its children's sum sends the top draws through the no-hit
    clamp onto padding nodes, past the end of the level below."""
    spec = sumtree.make_spec(capacity, fanout)
    rng = np.random.default_rng(capacity)
    pri = rng.uniform(0.0, 2.0, capacity).astype(np.float32)
    pri[rng.random(capacity) < 0.3] = 0.0
    tree = sumtree.build(spec, jnp.asarray(pri))
    tree = tree.at[0].multiply(root_scale)
    tail = np.array([0.0, 1e-9, 1.0 - 1e-7, 1.0], np.float32)
    u = jnp.asarray(np.concatenate(
        [rng.uniform(0, 1, 256).astype(np.float32), tail]))
    new = jax.jit(functools.partial(sumtree.sample, spec))(tree, u)
    old = jax.jit(functools.partial(_sample_flat_slices, spec))(tree, u)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(old[1]))
