"""Device math of the continuous-batching actor server (DESIGN.md §13).

The backbone's ``decode_step`` keeps ONE scalar ``cache["pos"]`` for the
whole batch — correct for the training actor (lockstep episodes), wrong
for serving, where every sequence in the batch sits at a different
depth.  Rather than rewriting five model families, the engine vmaps a
batch-of-1 ``token_dqn.serve_step`` over the slot axis: each slot's
cache slice carries its *own* ``pos``, so RoPE phases, cache writes and
causal masks are all per-slot — bit-exact against the plain batched
decode when positions happen to agree (pinned in tests/test_serve.py).

Three jitted entry points, three bounded compile sets:

* ``_prime``   — bucket-padded prefill of one request into a fresh slot
                 cache, ``pos`` rewound to the true prompt length.  One
                 retrace per *bucket edge* (shapes are the bucket set —
                 repro-lint R401-clean by construction, asserted via the
                 compile-counter spy in tests).
* ``_insert``/``_release`` — slot-table edits at a dynamic slot index
                 (one compile each).
* ``_step``    — the vmapped decode over all slots, free slots frozen by
                 the ``slot_mask`` (one compile).  The batched KV cache
                 is donated: serving holds exactly one live cache buffer.

Families: dense | moe only.  The pad-then-rewind trick needs state that
is purely position-indexed — recurrent families (ssm, hybrid) fold pad
tokens into their state irreversibly, and vlm/audio prompts carry extra
embeddings the request queue doesn't model.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.agents import token_dqn
from repro.models import backbone
from repro.models.config import NO_SHARDING, ModelConfig
from repro.serve.buckets import BucketSpec

Pytree = Any

SUPPORTED_FAMILIES = ("dense", "moe")


class DecodeState(NamedTuple):
    """Per-slot serving state: the stacked slot caches (leaf axis 0 =
    slot), each slot's next input token, and the busy mask."""

    cache: Pytree                 # leaves: (slots, ...per-slot cache...)
    tokens: jax.Array             # (slots, 1, 1) int32
    active: jax.Array             # (slots,) bool


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, shd=NO_SHARDING, *, slots: int,
                 max_len: int, buckets: BucketSpec):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise ValueError(
                f"DecodeEngine serves {SUPPORTED_FAMILIES} families only, "
                f"got {cfg.family!r} ({cfg.name}): pad-then-rewind needs a "
                "purely position-indexed cache (DESIGN.md §13)")
        if slots < 1:
            raise ValueError(f"slots={slots}: must be >= 1")
        if buckets.max_prompt_len > max_len:
            raise ValueError(
                f"largest bucket edge {buckets.max_prompt_len} exceeds "
                f"max_len={max_len}: prefill could not fit in the cache")
        self.cfg = cfg
        self.shd = shd
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.buckets = buckets

        def prime(params, padded, true_len):
            # bucket-padded prefill; first greedy action comes from the
            # last REAL position, and pos rewinds to the true length so
            # every pad key is overwritten before the mask can see it
            logits, cache = backbone.prefill(
                cfg, shd, params, padded, max_len=self.max_len)
            off = logits.shape[1] - padded.shape[1]
            last = jax.lax.dynamic_index_in_dim(
                logits[0], off + true_len - 1, axis=0, keepdims=False)
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return tok, dict(cache, pos=true_len.astype(jnp.int32))

        def insert(state: DecodeState, slot_cache, tok, slot) -> DecodeState:
            cache = jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_index_in_dim(
                    b, s.astype(b.dtype), slot, 0),
                state.cache, slot_cache)
            tokens = jax.lax.dynamic_update_index_in_dim(
                state.tokens, tok.reshape(1, 1), slot, 0)
            active = jax.lax.dynamic_update_index_in_dim(
                state.active, jnp.asarray(True), slot, 0)
            return DecodeState(cache, tokens, active)

        def release(state: DecodeState, slot) -> DecodeState:
            active = jax.lax.dynamic_update_index_in_dim(
                state.active, jnp.asarray(False), slot, 0)
            return DecodeState(state.cache, state.tokens, active)

        self._prime = jax.jit(prime)
        self._insert = jax.jit(insert)
        self._release = jax.jit(release)
        # one decode program for the whole slot table; per-slot pos lives
        # in the vmapped cache slice, free slots frozen by the slot mask.
        # The old cache buffer is donated — exactly one live KV cache.
        self._step = jax.jit(
            jax.vmap(functools.partial(token_dqn.serve_step, cfg, shd),
                     in_axes=(None, 0, 0, 0)),
            donate_argnums=(1,))

    # -- state ---------------------------------------------------------------

    def init_state(self) -> DecodeState:
        slot = backbone.init_cache(self.cfg, self.shd, 1, self.max_len)
        cache = jax.tree.map(
            lambda x: jnp.stack([x] * self.slots), slot)
        return DecodeState(
            cache=cache,
            tokens=jnp.zeros((self.slots, 1, 1), jnp.int32),
            active=jnp.zeros((self.slots,), bool),
        )

    def fits(self, prompt_len: int, max_new_tokens: int) -> None:
        """Admission-time capacity check (raises on violation): the
        prompt must land in a bucket and the last decode write at
        ``prompt_len + max_new_tokens - 2`` must stay inside the cache."""
        self.buckets.bucket_for(prompt_len)   # raises past the last edge
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens}: must be >= 1")
        if prompt_len + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt_len={prompt_len} + max_new_tokens={max_new_tokens} "
                f"- 1 exceeds max_len={self.max_len}: the generation would "
                "overrun the KV cache")

    # -- ops -----------------------------------------------------------------

    def prime(self, params, prompt: np.ndarray) -> Tuple[jax.Array, Pytree]:
        """Bucket-padded prefill of one prompt → (first greedy token,
        slot cache with pos = true length)."""
        prompt = np.asarray(prompt, np.int32)
        padded = self.buckets.pad(prompt)
        return self._prime(params, jnp.asarray(padded),
                           jnp.asarray(prompt.shape[0], jnp.int32))

    def insert(self, state: DecodeState, slot: int, slot_cache,
               tok) -> DecodeState:
        return self._insert(state, slot_cache, jnp.asarray(tok, jnp.int32),
                            jnp.asarray(slot, jnp.int32))

    def release(self, state: DecodeState, slot: int) -> DecodeState:
        return self._release(state, jnp.asarray(slot, jnp.int32))

    def step(self, params, state: DecodeState) -> Tuple[jax.Array, DecodeState]:
        """One continuous-batching decode step over every slot; free
        slots are frozen in place by the slot mask."""
        actions, cache = self._step(params, state.cache, state.tokens,
                                    state.active)
        actions = actions.reshape(self.slots)
        state = DecodeState(
            cache=cache,
            tokens=actions.astype(jnp.int32).reshape(self.slots, 1, 1),
            active=state.active)
        return actions, state

    # -- retrace accounting ---------------------------------------------------

    @property
    def prime_compiles(self) -> int:
        """Bounded by ``len(buckets.edges)`` — the §13 retrace invariant."""
        return int(self._prime._cache_size())

    @property
    def decode_compiles(self) -> int:
        return int(self._step._cache_size())
