"""Pallas TPU kernel: batched prefix-sum descent over the K-ary sum tree.

TPU adaptation of the paper's cache-aligned sibling scan (§IV-C3/C4):

  * every level is a ``(groups, K)`` matrix — one sibling group per row;
    with K = 128 a row is exactly one lane-aligned VREG row (the paper's
    cache line);
  * per-sample row gather is a **one-hot MXU matmul**
    ``one_hot(group_idx, G) @ level`` — TPUs have no efficient scalar
    gather, so the "minimise cache misses" goal becomes "turn the
    irregular access into a dense systolic op";
  * the linear child scan becomes a lane-parallel prefix sum — a second
    MXU matmul against the upper-triangular (K, K) ones matrix (Mosaic
    has no ``cumsum``) — plus a first-hit lane-min over the 128-lane row;
  * per-draw vectors are ``(SB, 1)`` columns, so every block Mosaic sees
    is 2-D and tiles at any padded batch;
  * all levels are VMEM-resident (BlockSpec index_map pinned to block 0,
    single-buffered); the grid streams sample blocks of ``SB`` draws.

VMEM budget: tree bytes + SB·G_leaf·4 (one-hot) + transient rows.  The
``ops.py`` wrapper falls back to the XLA path when the tree exceeds the
kernel budget (documented limit; at that size HBM gathers dominate and
XLA's native gather is the right tool).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SAMPLE_BLOCK = 128  # SB — samples per grid step
_HIGHEST = jax.lax.Precision.HIGHEST


def resident_spec(shape) -> pl.BlockSpec:
    """Whole-array block pinned to block 0 for every grid step, held in
    one VMEM buffer (it never changes, so double-buffering would only
    double its footprint)."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(shape, lambda *_: zeros,
                        pipeline_mode=pl.Buffered(1))


def compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    """Raise Mosaic's scoped-VMEM limit to the kernel's working set (the
    default scoped limit is far below a 2^20-leaf tree plus its
    one-hots), with headroom, capped below the physical VMEM."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(100 * 2**20, 2 * vmem_bytes + 16 * 2**20)))


def descend(level_vals, u, *, capacity: int, fanout: int):
    """Shared in-kernel inverse-CDF descent over loaded level matrices.

    ``level_vals[l]``: (groups_l, K) f32, top-down below the root (leaf
    level last).  ``u``: (SB, 1).  Returns (leaf, pri) as (SB, 1)
    columns — also used by the fused sample+gather kernel
    (sample_gather.py), so the two kernels cannot drift apart
    numerically.
    """
    k = fanout
    sb = u.shape[0]
    total = jnp.sum(level_vals[0], axis=-1, keepdims=True)  # children of root
    residual = jnp.clip(u, 1e-12, 1.0 - 1e-7) * total       # (SB, 1)
    group = jnp.zeros((sb, 1), jnp.int32)

    lane = jax.lax.broadcasted_iota(jnp.int32, (sb, k), 1)
    lane_f = lane.astype(jnp.float32)
    # tri[i, j] = 1 iff i <= j: rows @ tri is the inclusive prefix sum
    tri = (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
           ).astype(jnp.float32)
    row_val = jnp.zeros((sb, 1), jnp.float32)
    for lv in level_vals:                          # (G, K) per level
        g = lv.shape[0]
        giota = jax.lax.broadcasted_iota(jnp.int32, (sb, g), 1)
        onehot = (group == giota).astype(jnp.float32)
        rows = jax.lax.dot(onehot, lv, precision=_HIGHEST)  # (SB, K)
        csum = jax.lax.dot(rows, tri, precision=_HIGHEST)   # prefix sums
        hit = csum >= residual
        # first hit lane; no hit (fp rounding at the tail) clamps to K-1
        first = jnp.min(jnp.where(hit, lane_f, float(k)), axis=-1,
                        keepdims=True)
        cutoff = jnp.minimum(first, float(k - 1)).astype(jnp.int32)
        sel = (lane == cutoff).astype(jnp.float32)
        picked = jnp.sum(csum * sel, axis=-1, keepdims=True)
        row_val = jnp.sum(rows * sel, axis=-1, keepdims=True)
        residual = residual - (picked - row_val)   # drop prefix before cutoff
        group = group * k + cutoff

    leaf = jnp.minimum(group, capacity - 1)
    # Parity with the XLA path (core/sumtree.py), which re-reads the
    # priority AFTER clamping: an fp-tail draw whose no-hit clamps cascade
    # into the leaf-level padding has row_val = 0 (the padding lane), but
    # the clamped leaf is `capacity - 1`, whose priority is a static
    # (group, lane) read of the leaf level — `lv` still holds the loop's
    # last (leaf-level) load, so no second VMEM read of the largest level.
    r, c = (capacity - 1) // k, (capacity - 1) % k
    r8 = (r // 8) * 8                              # sublane-aligned slice
    tile = lv[r8:r8 + 8] if lv.shape[0] >= r8 + 8 else lv[r8:]
    pick = ((jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == r - r8)
            & (jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == c))
    clamp_val = jnp.sum(jnp.where(pick, tile, 0.0), keepdims=True)  # (1, 1)
    pri = jnp.where(group > capacity - 1, clamp_val, row_val)
    return leaf, pri


def _kernel(capacity: int, fanout: int, u_ref, *refs):
    """refs = (level_1, ..., level_H, out_idx, out_pri)."""
    level_refs = refs[:-2]
    out_idx_ref, out_pri_ref = refs[-2:]
    level_vals = [ref[...].astype(jnp.float32) for ref in level_refs]
    u = u_ref[...].astype(jnp.float32)
    leaf, pri = descend(level_vals, u, capacity=capacity, fanout=fanout)
    out_idx_ref[...] = leaf
    out_pri_ref[...] = pri


def descent_vmem_bytes(levels, sb: int = SAMPLE_BLOCK) -> int:
    """VMEM the descent holds: the resident levels plus the widest
    one-hot and a few (SB, K) transients."""
    tree = sum(lv.size for lv in levels) * 4
    widest = max(lv.shape[0] for lv in levels)
    return tree + sb * widest * 4 + 8 * sb * levels[0].shape[1] * 4


def sumtree_sample_levels(
    levels: Sequence[jax.Array],
    u: jax.Array,
    *,
    capacity: int,
    fanout: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sample ``u.shape[0]`` leaves from level matrices (top-down, no root).

    ``levels[l]`` has shape (groups_l, K); ``levels[-1]`` is the leaf level.
    B must be a multiple of SAMPLE_BLOCK (ops.py pads).
    """
    b = u.shape[0]
    assert b % SAMPLE_BLOCK == 0, b
    col = pl.BlockSpec((SAMPLE_BLOCK, 1), lambda i: (i, 0))
    idx, pri = pl.pallas_call(
        functools.partial(_kernel, capacity, fanout),
        grid=(b // SAMPLE_BLOCK,),
        in_specs=[col] + [resident_spec(lv.shape) for lv in levels],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        compiler_params=compiler_params(descent_vmem_bytes(levels)),
        interpret=interpret,
        name="sumtree_sample",
    )(u.reshape(b, 1), *levels)
    return idx[:, 0], pri[:, 0]
