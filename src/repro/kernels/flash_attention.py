"""Pallas TPU kernels: FlashAttention-style fused attention, fwd + bwd (§Perf).

Beyond-paper optimization for the learner's dominant memory term: the
paper-faithful baseline materializes (…, S, S) f32 scores in HBM; these
kernels stream K/V blocks through VMEM with an online-softmax
accumulator, so attention's HBM traffic collapses to Q/K/V/O (+ the
(N, S) logsumexp saved for the backward).

Three kernels (classic FlashAttention-2 decomposition):
  * fwd  — grid (N, S/BQ, S/BK), output block revisited over K; scratch
           m/l/acc in VMEM; emits O and LSE.
  * dq   — grid (N, S/BQ, S/BK), accumulates dQ over K blocks.
  * dkv  — grid (N, S/BK, S/BQ), accumulates dK/dV over Q blocks.

Causal, sliding-window and chunked-local (Llama-4) masks are computed
from global block offsets; fully-masked blocks are skipped.  Tied
together with ``jax.custom_vjp``; validated in interpret mode against
``ref.flash_attention_ref`` (values AND gradients).
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Dry-run cost modeling (launch/dryrun.py sets this): on CPU the kernels
# run in interpret mode, which lowers to an XLA grid loop whose HBM
# accounting bears no relation to the real TPU custom call.  The stub is
# a shape/dataflow-exact stand-in (reads Q/K/V, writes O; AD reads dO,
# writes dQ/dK/dV) — never *executed*, only lowered; FLOPs are added
# analytically (hlo_analysis.flash_attention_flops).
_STUB = os.environ.get("REPRO_FLASH_STUB") == "1"

BQ = 512
BK = 512
NEG = -1e30
# the (1,) int32 per-layer global flag is a scalar read: keep it in SMEM
_GLOB_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _block_mask(attention, window, causal, glob, q_pos, k_pos):
    """glob may be a traced scalar (per-layer global-attention flag)."""
    mask = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        mask &= k_pos <= q_pos
    if attention == "sliding":
        mask &= glob | (k_pos > q_pos - window)
    if attention == "chunked":
        mask &= glob | ((k_pos // window) == (q_pos // window))
    return mask


def _block_reachable(attention, window, causal, glob,
                     q_start, bq, k_start, bk):
    q_last = q_start + bq - 1
    k_last = k_start + bk - 1
    reach = jnp.asarray(True)
    if causal:
        reach &= k_start <= q_last
    if attention == "sliding":
        reach &= glob | (k_last > q_start - window)
    if attention == "chunked":
        reach &= glob | (((k_start // window) <= (q_last // window)) & (
            (k_last // window) >= (q_start // window)))
    return reach


# ------------------------------------------------------------------ fwd ----

def _fwd_kernel(attention, window, causal, scale,
                g_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    q_start = pl.program_id(1) * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    glob = g_ref[0] != 0
    @pl.when(_block_reachable(attention, window, causal, glob,
                              q_start, bq, k_start, bk))
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot(q, k.T) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(_block_mask(attention, window, causal, glob,
                                  q_pos, k_pos), s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        lsum = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / lsum).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(lsum)          # (BQ, 1)


def _fwd(q, k, v, glob, attention, window, causal, bq, bk, interpret):
    n, s, hd = q.shape
    sk = k.shape[1]
    bq_, bk_ = min(bq, s), min(bk, sk)
    assert s % bq_ == 0 and sk % bk_ == 0, (s, sk, bq_, bk_)
    scale = 1.0 / math.sqrt(hd)
    grid = (n, s // bq_, sk // bk_)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, attention, window, causal, scale),
        grid=grid,
        in_specs=[
            _GLOB_SPEC,
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, s, hd), q.dtype),
            jax.ShapeDtypeStruct((n, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, hd), jnp.float32),
        ],
        interpret=interpret,
    )(glob, q, k, v)
    return o, lse


# ------------------------------------------------------------------- dq ----

def _dq_kernel(attention, window, causal, scale,
               g_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    q_start = pl.program_id(1) * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    glob = g_ref[0] != 0
    @pl.when(_block_reachable(attention, window, causal, glob,
                              q_start, bq, k_start, bk))
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                 # (BQ, 1)
        delta = delta_ref[0]
        s = jax.lax.dot(q, k.T) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = _block_mask(attention, window, causal, glob, q_pos, k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        ds = p * (jax.lax.dot(do, v.T) - delta)
        acc_scr[...] = acc_scr[...] + jax.lax.dot(ds, k) * scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


# ------------------------------------------------------------------ dkv ----

def _dkv_kernel(attention, window, causal, scale,
                g_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    bk, bq = k_ref.shape[1], q_ref.shape[1]
    k_start = pl.program_id(1) * bk
    q_start = qi * bq

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    glob = g_ref[0] != 0
    @pl.when(_block_reachable(attention, window, causal, glob,
                              q_start, bq, k_start, bk))
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                 # (BQ, 1)
        delta = delta_ref[0]
        s = jax.lax.dot(q, k.T) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = _block_mask(attention, window, causal, glob, q_pos, k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_scr[...] = dv_scr[...] + jax.lax.dot(p.T, do)
        ds = p * (jax.lax.dot(do, v.T) - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot(ds.T, q) * scale

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(attention, window, causal, bq, bk, interpret, res, do):
    q, k, v, o, lse, glob = res
    n, s, hd = q.shape
    sk = k.shape[1]
    bq_, bk_ = min(bq, s), min(bk, sk)
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1,
                    keepdims=True)                      # (N, S, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, attention, window, causal, scale),
        grid=(n, s // bq_, sk // bk_),
        in_specs=[
            _GLOB_SPEC,
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, hd), jnp.float32)],
        interpret=interpret,
    )(glob, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, attention, window, causal, scale),
        grid=(n, sk // bk_, s // bq_),
        in_specs=[
            _GLOB_SPEC,
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_, hd), jnp.float32),
            pltpu.VMEM((bk_, hd), jnp.float32),
        ],
        interpret=interpret,
    )(glob, q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, glob, attention, window, causal, bq, bk, interpret):
    o, _ = _fwd(q, k, v, glob, attention, window, causal, bq, bk, interpret)
    return o


def _vjp_fwd(q, k, v, glob, attention, window, causal, bq, bk, interpret):
    o, lse = _fwd(q, k, v, glob, attention, window, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse, glob)


def _vjp_bwd(attention, window, causal, bq, bk, interpret, res, do):
    dq, dk, dv = _bwd(attention, window, causal, bq, bk, interpret, res, do)
    return dq, dk, dv, None


_flash.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention_nhsd(q, k, v, attention="full", window=0, causal=True,
                         is_global=True, bq=BQ, bk=BK, interpret=False):
    """Fused attention on (N, S, hd) tensors (N = batch·heads).

    ``is_global`` may be a python bool or a traced scalar (per-layer
    global-attention flag from a scanned layer stack)."""
    if _STUB:
        eps = jnp.asarray(1e-12, q.dtype)
        return q + eps * k + eps * v   # dataflow-exact dry-run stand-in
    glob = jnp.asarray([is_global], jnp.int32) if not isinstance(
        is_global, jax.Array) else is_global.reshape(1).astype(jnp.int32)
    return _flash(q, k, v, glob, attention, window, causal, bq, bk, interpret)
