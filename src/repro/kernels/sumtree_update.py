"""Pallas TPU kernel: batched priority update with upward delta propagation.

TPU adaptation of paper Alg. 2 UPDATEVALUE + Alg. 3 synchronization:

  * scatter of per-update deltas into each ancestor level is a **one-hot
    MXU matmul**: ``one_hot(group).T @ (delta ⊙ one_hot(child))`` produces
    a dense (groups, K) delta matrix accumulated into the VMEM-resident
    level — the systolic replacement for lock-protected scatter;
  * duplicate leaf indices are resolved to last-writer-wins *before* the
    kernel launches: the wrapper (ops.py) computes the sort-based
    last-writer mask over the whole batch (core/sumtree.py) and passes
    it in, so at most one entry per leaf carries a non-zero delta.  The
    old in-kernel O(UB²) triangular dedup and the delta-neutral padding
    dance are gone — padded entries simply arrive with mask 0;
  * levels are aliased input↔output (in-place tree update).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sumtree_sample import compiler_params

UPDATE_BLOCK = 128  # UB — updates per grid step


def _kernel(fanout: int, idx_ref, val_ref, mask_ref, *refs):
    """refs = (root_out, level_1_out, ..., level_H_out), aliased to inputs.
    Per-update vectors are (UB, 1) columns."""
    root_ref = refs[0]
    level_refs = refs[1:]
    k = fanout
    ub = idx_ref.shape[0]

    idx = idx_ref[...]
    val = val_ref[...].astype(jnp.float32)
    # Full-batch last-writer mask (precomputed sort-based merge in the
    # wrapper): 1.0 on the single surviving write per leaf, 0.0 on
    # superseded duplicates and padding.
    mask = mask_ref[...].astype(jnp.float32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (ub, k), 1)

    # Leaf level: read old values (MXU gather), compute masked deltas, set.
    leaf_ref = level_refs[-1]
    leaf = leaf_ref[...].astype(jnp.float32)       # (G_H, K)
    g_h = leaf.shape[0]
    g = idx // k
    c = idx % k
    giota = jax.lax.broadcasted_iota(jnp.int32, (ub, g_h), 1)
    oh_g = (g == giota).astype(jnp.float32)                 # (UB, G_H)
    oh_c = (c == lane).astype(jnp.float32)                  # (UB, K)
    rows = jax.lax.dot(oh_g, leaf, precision=jax.lax.Precision.HIGHEST)
    old = jnp.sum(rows * oh_c, axis=-1, keepdims=True)
    delta = (val - old) * mask                              # (UB, 1)
    scat = jax.lax.dot(                                     # (G_H, K) scatter
        oh_g.T, delta * oh_c, precision=jax.lax.Precision.HIGHEST
    )
    leaf_ref[...] = (leaf + scat).astype(leaf_ref.dtype)

    # Intermediate levels: pure scatter-add of deltas (duplicates sum).
    node = g
    for ref in level_refs[-2::-1]:
        lv = ref[...].astype(jnp.float32)
        g_l = lv.shape[0]
        g2 = node // k
        c2 = node % k
        giota2 = jax.lax.broadcasted_iota(jnp.int32, (ub, g_l), 1)
        oh_g2 = (g2 == giota2).astype(jnp.float32)
        oh_c2 = (c2 == lane).astype(jnp.float32)
        scat2 = jax.lax.dot(
            oh_g2.T, delta * oh_c2, precision=jax.lax.Precision.HIGHEST
        )
        ref[...] = (lv + scat2).astype(ref.dtype)
        node = g2

    # Padded root group: root value at (0, 0).
    root = root_ref[...].astype(jnp.float32)                # (1, K)
    zero_lane = (jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) == 0)
    root_ref[...] = (
        root + jnp.where(zero_lane, jnp.sum(delta), 0.0)
    ).astype(root_ref.dtype)


def sumtree_update_levels(
    root: jax.Array,
    levels: Sequence[jax.Array],
    idx: jax.Array,
    values: jax.Array,
    mask: jax.Array,
    *,
    fanout: int,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """SET priorities at ``idx`` and propagate deltas to every level + root.

    ``root``: (1, K) padded root group.  ``levels[l]``: (groups_l, K),
    leaf level last.  ``mask``: int32 0/1, the full-batch last-writer
    mask (padding entries 0).  Returns updated (root, *levels).  B must
    be a multiple of UPDATE_BLOCK (ops.py pads with masked-out entries).
    """
    b = idx.shape[0]
    assert b % UPDATE_BLOCK == 0, b
    tree_in = [root, *levels]
    # the tree is an accumulator revisited by every grid step, so its
    # blocks stay double-buffered (in and out); add the (UB, G) one-hots
    # and the (G, K) scatter of the widest level
    tree_bytes = sum(t.size for t in tree_in) * 4
    widest = max(t.shape[0] for t in tree_in)
    vmem = 4 * tree_bytes + 3 * widest * max(UPDATE_BLOCK, fanout) * 4
    tree_specs = [pl.BlockSpec(t.shape, lambda i: (0, 0)) for t in tree_in]
    col = pl.BlockSpec((UPDATE_BLOCK, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, fanout),
        grid=(b // UPDATE_BLOCK,),
        in_specs=[col, col, col] + tree_specs,
        out_specs=tree_specs,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tree_in],
        input_output_aliases={3 + j: j for j in range(len(tree_in))},
        compiler_params=compiler_params(vmem),
        interpret=interpret,
        name="sumtree_update",
    )(idx.reshape(b, 1), values.reshape(b, 1), mask.reshape(b, 1), *tree_in)
