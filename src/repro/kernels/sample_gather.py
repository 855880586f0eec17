"""Pallas TPU kernel: fused inverse-CDF sample + prioritized gather.

The paper's Sampling step is two irregular-memory phases — descend the
sum tree, then fetch the sampled transitions from storage (Table I).
The split kernels (sumtree_sample.py + gather.py) round-trip the sampled
indices through HBM between two kernel launches; this kernel fuses both
phases, so the indices are produced and consumed inside one grid:

  * grid = (B / SB sample blocks, N / NB storage steps), storage steps
    innermost;
  * at storage step 0 the block runs the shared descent
    (``sumtree_sample.descend`` — the same code path as the split
    kernel, so the two cannot drift) over the VMEM-resident levels and
    writes ``out_idx``/``out_pri``;
  * every storage step (including step 0) then re-reads ``out_idx``
    from its pinned output block — never from HBM — and accumulates
    ``one_hot(idx ∈ block) @ storage_block`` into each storage leaf's
    pinned output block (the gather.py accumulator pattern, one shared
    one-hot for *all* leaves instead of one per gather call).

Storage leaves are streamed as f32 (N, F) matrices; integer payloads are
exact below 2^24 (one-hot matmul sums in f32 — same contract as
gather.py).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sumtree_sample import (compiler_params, descend,
                                          descent_vmem_bytes, resident_spec)

SAMPLE_BLOCK = 128   # SB — draws per sample block
STORAGE_BLOCK = 512  # NB — storage rows per streaming step


def _kernel(capacity: int, fanout: int, n_levels: int,
            u_ref, *refs):
    """refs = (level_1..level_H, storage_0..storage_L,
               out_idx, out_pri, gathered_0..gathered_L)."""
    level_refs = refs[:n_levels]
    n_storage = (len(refs) - n_levels - 2) // 2
    storage_refs = refs[n_levels:n_levels + n_storage]
    out_idx_ref = refs[n_levels + n_storage]
    out_pri_ref = refs[n_levels + n_storage + 1]
    gathered_refs = refs[n_levels + n_storage + 2:]

    n_step = pl.program_id(1)
    nb = storage_refs[0].shape[0]
    sb = u_ref.shape[0]

    @pl.when(n_step == 0)
    def _descend_and_init():
        level_vals = [ref[...].astype(jnp.float32) for ref in level_refs]
        u = u_ref[...].astype(jnp.float32)
        leaf, pri = descend(level_vals, u, capacity=capacity, fanout=fanout)
        out_idx_ref[...] = leaf
        out_pri_ref[...] = pri
        for g_ref in gathered_refs:
            g_ref[...] = jnp.zeros_like(g_ref)

    # idx comes from the pinned output block (same block ∀ storage steps)
    # — written above at step 0, persistent across the inner grid axis.
    idx = out_idx_ref[...]                                  # (SB, 1)
    local = idx - n_step * nb
    niota = jax.lax.broadcasted_iota(jnp.int32, (sb, nb), 1)
    onehot = (local == niota).astype(jnp.float32)           # 0 out of block
    for s_ref, g_ref in zip(storage_refs, gathered_refs):
        block = s_ref[...].astype(jnp.float32)              # (NB, F)
        acc = jax.lax.dot(onehot, block,
                          precision=jax.lax.Precision.HIGHEST)
        g_ref[...] = g_ref[...] + acc.astype(g_ref.dtype)


def sample_gather_levels(
    levels: Sequence[jax.Array],
    u: jax.Array,
    storage_mats: Sequence[jax.Array],
    *,
    capacity: int,
    fanout: int,
    interpret: bool = False,
):
    """Sample ``u.shape[0]`` leaves and gather their storage rows.

    ``levels[l]``: (groups_l, K), top-down below the root, leaf level
    last (sumtree_sample layout).  ``storage_mats[j]``: f32 (N, F_j)
    with one shared padded row count N (a multiple of STORAGE_BLOCK).
    B must be a multiple of SAMPLE_BLOCK (ops.py pads).  Returns
    (idx, pri, [gathered_j]).
    """
    b = u.shape[0]
    assert b % SAMPLE_BLOCK == 0, b
    n = storage_mats[0].shape[0]
    assert n % STORAGE_BLOCK == 0, n
    assert all(m.shape[0] == n for m in storage_mats)
    grid = (b // SAMPLE_BLOCK, n // STORAGE_BLOCK)

    storage_specs = [
        pl.BlockSpec((STORAGE_BLOCK, m.shape[1]), lambda i, j: (j, 0))
        for m in storage_mats
    ]
    gathered_specs = [
        pl.BlockSpec((SAMPLE_BLOCK, m.shape[1]), lambda i, j: (i, 0))
        for m in storage_mats
    ]
    col = pl.BlockSpec((SAMPLE_BLOCK, 1), lambda i, j: (i, 0))
    out_shapes = (
        [jax.ShapeDtypeStruct((b, 1), jnp.int32),
         jax.ShapeDtypeStruct((b, 1), jnp.float32)]
        + [jax.ShapeDtypeStruct((b, m.shape[1]), jnp.float32)
           for m in storage_mats]
    )
    # streamed storage blocks and gathered accumulators are
    # double-buffered (F lanes pad to 128 in VMEM)
    stream = sum(2 * (STORAGE_BLOCK + SAMPLE_BLOCK) * pl.cdiv(m.shape[1], 128)
                 * 128 * 4 for m in storage_mats)
    out = pl.pallas_call(
        functools.partial(_kernel, capacity, fanout, len(levels)),
        grid=grid,
        in_specs=([col] + [resident_spec(lv.shape) for lv in levels]
                  + storage_specs),
        out_specs=[col, col] + gathered_specs,
        out_shape=out_shapes,
        compiler_params=compiler_params(descent_vmem_bytes(levels) + stream),
        interpret=interpret,
        name="sample_gather",
    )(u.reshape(b, 1), *levels, *storage_mats)
    idx, pri, *gathered = out
    return idx[:, 0], pri[:, 0], gathered
