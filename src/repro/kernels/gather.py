"""Pallas TPU kernel: prioritized minibatch assembly (storage gather).

The paper's "access the storage" step of Sampling (Table I).  Random
HBM reads of sampled transitions are the irregular-access hot spot; on
TPU we stream the storage through VMEM in blocks and assemble the batch
with one-hot MXU matmuls:

    out[b_block] = Σ_n  one_hot(idx_block ∈ n_block) @ storage[n_block]

Grid = (N / NB) storage steps × (B / BB) batch blocks; the output block
is revisited across the N dimension (accumulator pattern).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BATCH_BLOCK = 128   # BB
STORAGE_BLOCK = 512  # NB


def _kernel(idx_ref, storage_ref, out_ref):
    n_step = pl.program_id(1)
    nb = storage_ref.shape[0]

    @pl.when(n_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                               # (BB, 1) global indices
    local = idx - n_step * nb                        # position inside block
    niota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], nb), 1)
    onehot = (local == niota).astype(jnp.float32)    # 0 if out of block
    block = storage_ref[...].astype(jnp.float32)     # (NB, F)
    acc = jax.lax.dot(onehot, block, precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = out_ref[...] + acc.astype(out_ref.dtype)


def gather_rows(
    storage: jax.Array,
    idx: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = storage[idx[i]] for 2D storage (N, F).

    Exact for f32/bf16 payloads and for integer payloads with values
    < 2^24 (one-hot matmul sums are exact in f32).  B and N must be
    multiples of the block sizes (ops.py pads).
    """
    n, f = storage.shape
    b = idx.shape[0]
    assert b % BATCH_BLOCK == 0 and n % STORAGE_BLOCK == 0, (b, n)
    grid = (b // BATCH_BLOCK, n // STORAGE_BLOCK)

    out_dtype = storage.dtype
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BATCH_BLOCK, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((STORAGE_BLOCK, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BATCH_BLOCK, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f), out_dtype),
        interpret=interpret,
        name="gather_rows",
    )(idx.reshape(b, 1), storage)
