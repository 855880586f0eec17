"""K-ary sum tree — the paper's core data structure (§IV), in JAX.

Layout (paper §IV-C4, adapted to TPU):
  * Implicit, pointer-free: one flat f32 array holding all levels
    concatenated top-down.  ``offsets[l]`` is the start of level ``l``.
  * Every sibling group of K children is contiguous and starts at a
    multiple of K.  On CPU the paper aligns groups to cache lines
    (``K % C == 0``); on TPU we align to the 128-lane vector register row
    (default ``K = 128``), so one descent step reads exactly one aligned
    (1, 128) row — the TPU analogue of "one cache line per level".
  * The root is padded to a full group of K ("pad the root node with K-1
    so that it is also cache aligned") — level 0 has K slots, root at 0.
  * One extra scratch slot is appended at the very end of the flat array;
    masked (duplicate) writes are dumped there, keeping every update a
    branch-free scatter.

Level sizes, bottom-up: ``m_H = ceil(N / K) * K`` leaves; each level above
has one node per child group, padded to a multiple of K; the topmost
non-root level has exactly K nodes (one group), whose parent is the root.

All operations are *batched*: the paper's asynchronous parallel
insert/sample/update from many threads becomes one data-parallel program
over B operations (DESIGN.md §2).  Batch semantics are defined to match
sequential application:
  * ``update``: duplicate indices resolve last-writer-wins;
  * ``sample``: pure read, order-free;
  * ``add``: duplicate indices accumulate.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_FANOUT = 128  # one VREG lane row; paper: K % cacheline == 0.


def _ceil_to(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


@dataclasses.dataclass(frozen=True)
class SumTreeSpec:
    """Static description of a K-ary sum tree (shapes only, no arrays)."""

    capacity: int            # number of usable leaves (N)
    fanout: int              # K
    level_sizes: Tuple[int, ...]   # padded node count per level, top-down
    offsets: Tuple[int, ...]       # flat-array offset of each level
    total_size: int                # flat array length (incl. scratch slot)

    @property
    def height(self) -> int:
        """Number of levels below the padded-root level."""
        return len(self.level_sizes) - 1

    @property
    def leaf_level(self) -> int:
        return len(self.level_sizes) - 1

    @property
    def leaf_offset(self) -> int:
        return self.offsets[self.leaf_level]

    @property
    def num_leaves(self) -> int:
        return self.level_sizes[self.leaf_level]

    @property
    def scratch_slot(self) -> int:
        return self.total_size - 1

    def groups(self, level: int) -> int:
        return self.level_sizes[level] // self.fanout


def make_spec(capacity: int, fanout: int = DEFAULT_FANOUT) -> SumTreeSpec:
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    if fanout < 2:
        raise ValueError(f"fanout must be >= 2, got {fanout}")
    sizes: List[int] = [_ceil_to(capacity, fanout)]
    # Build upward until a single group of K remains.
    while sizes[0] > fanout:
        groups = sizes[0] // fanout
        sizes.insert(0, _ceil_to(groups, fanout))
    # Padded root level (paper: root padded to one full group).
    sizes.insert(0, fanout)
    offsets = list(np.cumsum([0] + sizes[:-1]))
    total = int(np.sum(sizes)) + 1  # +1 scratch slot for masked writes
    return SumTreeSpec(
        capacity=capacity,
        fanout=fanout,
        level_sizes=tuple(int(s) for s in sizes),
        offsets=tuple(int(o) for o in offsets),
        total_size=total,
    )


def init(spec: SumTreeSpec, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros((spec.total_size,), dtype=dtype)


def total(spec: SumTreeSpec, tree: jax.Array) -> jax.Array:
    """Σ priorities — the root value, Θ(1) (paper §IV-A2)."""
    return tree[0]


def get(spec: SumTreeSpec, tree: jax.Array, idx: jax.Array) -> jax.Array:
    """Priority retrieval, Θ(1) per index (paper §IV-C1)."""
    return tree[spec.leaf_offset + idx]


def last_writer_mask(idx: jax.Array, num_slots: int | None = None) -> jax.Array:
    """mask[i] = True iff no j > i has idx[j] == idx[i].

    Resolves duplicate indices in a batched update to sequential
    last-writer-wins semantics (DESIGN.md §2: lock-free conflict
    resolution).  Sort-based, O(B log B): sort (idx, position) pairs and
    mark the last entry of each equal-idx run (replaces the old O(B²)
    broadcast compare, which scaled quadratically with the op batch).

    ``num_slots`` — an exclusive upper bound on the index values — lets
    the two sort keys pack into one int32 (``idx * B + pos``), which XLA
    sorts substantially faster than a stable two-operand sort; without
    it (or when the packing would overflow int32) the stable key/value
    sort is used.  Both paths produce identical masks.
    """
    b = idx.shape[0]
    if b <= 1:
        return jnp.ones((b,), bool)
    idx = jnp.asarray(idx, jnp.int32)
    pos = jnp.arange(b, dtype=jnp.int32)
    if num_slots is not None and num_slots * b < 2**31:
        packed = jax.lax.sort(idx * b + pos)
        sidx, spos = packed // b, packed % b
    else:
        sidx, spos = jax.lax.sort_key_val(idx, pos, is_stable=True)
    run_end = jnp.concatenate([sidx[1:] != sidx[:-1], jnp.ones((1,), bool)])
    return jnp.zeros((b,), bool).at[spos].set(run_end)


def _ancestor_indices(spec: SumTreeSpec, idx: jax.Array) -> List[jax.Array]:
    """Node index of ``idx``'s ancestor at every level, top-down.

    Leaf i's parent at level H-1 is node i // K; and so on up.  Level 0 is
    the padded root (node 0 always).
    """
    out = [idx]
    cur = idx
    for _ in range(spec.leaf_level - 1, -1, -1):
        cur = cur // spec.fanout
        out.append(cur)
    return out[::-1]  # top-down: [root(=0s), ..., leaf idx]


def update(
    spec: SumTreeSpec,
    tree: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    *,
    unique: bool = False,
) -> jax.Array:
    """Batched priority SET (paper Alg. 2 UPDATEVALUE, vectorized).

    Sequential-equivalent semantics under duplicates (last writer wins).
    Θ((B + dedup) · log_K N) work; every scatter group is K-aligned.
    ``unique=True`` skips the dedup when the caller guarantees distinct
    indices (e.g. FIFO insert slots).

    This is the *eager* path: leaf write and upward propagation in one
    op.  The lazy-writing transaction path (``write_leaves`` + one
    ``rebuild`` per flush boundary, core/replay.py) coalesces many such
    ops into a single propagation pass per step.
    """
    idx = jnp.asarray(idx, jnp.int32)
    values = jnp.asarray(values, tree.dtype)
    mask = (jnp.ones(idx.shape, bool) if unique
            else last_writer_mask(idx, spec.num_leaves))
    old = tree[spec.leaf_offset + idx]
    delta = jnp.where(mask, values - old, jnp.zeros_like(values))
    # Leaf SET: masked duplicates are diverted to the scratch slot.
    leaf_target = jnp.where(mask, spec.leaf_offset + idx, spec.scratch_slot)
    tree = tree.at[leaf_target].set(values)
    # Upward delta propagation: scatter-ADD per level (duplicates sum).
    ancestors = _ancestor_indices(spec, idx)
    for level in range(spec.leaf_level - 1, -1, -1):
        node = ancestors[level]
        tree = tree.at[spec.offsets[level] + node].add(delta)
    return tree.at[spec.scratch_slot].set(0.0)


def write_leaves(
    spec: SumTreeSpec,
    tree: jax.Array,
    idx: jax.Array,
    values: jax.Array,
    *,
    unique: bool = False,
) -> jax.Array:
    """Leaf-only priority SET — the deferred half of a lazy write.

    Writes ``values`` into the leaf level (duplicates resolve
    last-writer-wins) and touches *nothing* above it: after this call
    the tree's interior no longer sums its leaves until ``rebuild``
    runs.  ``core/replay.py`` counts these deferred writes in its
    pending-delta ledger and flushes them in one merged propagation
    pass at the next sample boundary (paper §IV-D lazy writing).
    """
    idx = jnp.asarray(idx, jnp.int32)
    values = jnp.asarray(values, tree.dtype)
    if unique:
        return tree.at[spec.leaf_offset + idx].set(values)
    mask = last_writer_mask(idx, spec.num_leaves)
    leaf_target = jnp.where(mask, spec.leaf_offset + idx, spec.scratch_slot)
    tree = tree.at[leaf_target].set(values)
    return tree.at[spec.scratch_slot].set(0.0)


def rebuild(spec: SumTreeSpec, tree: jax.Array) -> jax.Array:
    """Recompute every interior level from the leaf level — one upward
    propagation pass (the ``TreeOps.flush`` payload).

    The interior becomes a *pure function of the current leaves*
    (K-aligned reshape-sums, the same reduction ``build`` uses), which
    is what makes lazy ≡ eager **bit-exact** at flush points: flushing
    after every write and flushing once after many writes reach the
    identical tree, because neither depends on the write history.  A
    side benefit over incremental delta propagation: f32 drift between
    interior sums and leaf sums cannot accumulate across steps.
    """
    level_vals = jax.lax.dynamic_slice(
        tree, (spec.leaf_offset,), (spec.num_leaves,))
    for level in range(spec.leaf_level - 1, -1, -1):
        groups = level_vals.shape[0] // spec.fanout
        parents = level_vals.reshape(groups, spec.fanout).sum(axis=-1)
        padded = jnp.zeros((spec.level_sizes[level],), tree.dtype)
        padded = padded.at[:groups].set(parents)
        tree = jax.lax.dynamic_update_slice(tree, padded,
                                            (spec.offsets[level],))
        level_vals = padded
    return tree


def add(
    spec: SumTreeSpec,
    tree: jax.Array,
    idx: jax.Array,
    deltas: jax.Array,
) -> jax.Array:
    """Batched priority increment (duplicates accumulate)."""
    idx = jnp.asarray(idx, jnp.int32)
    deltas = jnp.asarray(deltas, tree.dtype)
    ancestors = _ancestor_indices(spec, idx)
    for level in range(spec.leaf_level, -1, -1):
        tree = tree.at[spec.offsets[level] + ancestors[level]].add(deltas)
    return tree


def sample(
    spec: SumTreeSpec,
    tree: jax.Array,
    u: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Batched prefix-sum descent (paper Alg. 2 GETPREFIXSUMIDX).

    ``u`` ∈ [0, 1): B uniform draws.  Returns (leaf_idx, leaf_priority).
    Per level, reads exactly one K-aligned sibling row per sample and
    finds the cutoff node (Theorem 2) with a vectorized cumsum+argmax —
    the lane-parallel analogue of the paper's linear child scan.

    A descent step is one row gather from the level viewed as a
    ``(groups, K)`` matrix (a static slice of the flat tree, exact
    because sibling groups are K-aligned).  A 1-D ``dynamic_slice``
    per draw under ``vmap`` would compute the same rows, but XLA lowers
    its arbitrary start offsets to a serial loop over the draws per
    level on TPU.  A group past the level's end (the no-hit clamp can
    pick a padding node) reads the last row; such a draw ends beyond
    the capacity and takes the final leaf clamp either way.
    """
    u = jnp.asarray(u, tree.dtype)
    residual = jnp.clip(u, 1e-12, 1.0 - 1e-7) * tree[0]
    group = jnp.zeros(u.shape, jnp.int32)  # start: children of root = group 0
    k = spec.fanout

    for level in range(1, spec.leaf_level + 1):
        off, size = spec.offsets[level], spec.level_sizes[level]
        view = tree[off:off + size].reshape(size // k, k)
        rows = jnp.take(view, group, axis=0, mode="clip")  # (B, K) sibling rows
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


def build(spec: SumTreeSpec, priorities: jax.Array) -> jax.Array:
    """Bulk-build a tree from a dense (capacity,) priority vector."""
    pri = jnp.zeros((spec.num_leaves,), priorities.dtype)
    pri = pri.at[: spec.capacity].set(priorities)
    tree = init(spec, priorities.dtype)
    tree = jax.lax.dynamic_update_slice(tree, pri, (spec.leaf_offset,))
    return rebuild(spec, tree)


def leaves(spec: SumTreeSpec, tree: jax.Array) -> jax.Array:
    """Dense view of all usable leaf priorities, shape (capacity,)."""
    return jax.lax.dynamic_slice(tree, (spec.leaf_offset,), (spec.capacity,))


def check_invariant(spec: SumTreeSpec, tree: jax.Array, atol=1e-3) -> bool:
    """Every parent equals the sum of its children (test helper)."""
    t = np.asarray(tree)
    for level in range(spec.leaf_level):
        lo, size = spec.offsets[level], spec.level_sizes[level]
        nxt_lo, nxt_size = spec.offsets[level + 1], spec.level_sizes[level + 1]
        groups = nxt_size // spec.fanout
        child_sums = t[nxt_lo : nxt_lo + nxt_size].reshape(groups, spec.fanout).sum(-1)
        parents = t[lo : lo + size]
        if not np.allclose(parents[:groups], child_sums, atol=atol, rtol=1e-4):
            return False
        if level == 0 and not np.allclose(parents[1:], 0.0, atol=atol):
            return False
        if not np.allclose(parents[groups:], 0.0, atol=atol):
            return False
    return True
