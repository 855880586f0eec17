"""Parallel learners — the paper's parameter-server adaptation (§V-B).

Two execution styles:

  * **GSPMD (default)**: the learner batch is sharded over the data
    axes; jit + sharding constraints make XLA insert the gradient
    all-reduce.  Push(sub-gradients) + aggregate + pull(weights) of a
    parameter server on a torus *is* reduce-scatter + all-gather.

  * **shard_map (explicit)**: ``sharded_learn`` runs one learner per
    data-device with an explicit gradient ``pmean`` — used by the
    sharded-replay path where each learner samples from its local buffer
    shard.

On a 2-D ``("pod", "data")`` mesh the reduce is **hierarchical**
(DESIGN.md §7): gradients first reduce in f32 over the fast intra-pod
``data`` axis, then cross the slow inter-pod ``pod`` links through the
int8 error-feedback compressed reduce of ``optim/compress.py``
(``compressed_pmean``).  The EF buffer is explicit state threaded
through ``LoopState.ef_error`` — identical across the data shards of a
pod (they compress the same intra-pod partial), differing across pods.

The async-PS variant applies gradients with bounded staleness: actors
never block on the learner (the lazy-write invariant) and a learner
shard that misses ``max_staleness`` rounds is dropped from the reduce
(straggler mitigation — the reduce weight renormalizes).  That path is
``make_sharded_learn(..., max_staleness=...)``: each shard's gradient is
scaled by ``staleness_weights(age, max_staleness)`` and the psum is
renormalized by the total weight, so the realized reduce weights sum to
one whenever at least one shard is within the bound
(``staleness_reduce_weights``) and the update degrades to zero — params
held, never corrupted — when every shard is stale.  Composed with
compression, the weighted partial sums cross the pod axis as
``compressed_pmean × n_pods`` (mean × static pod count = the weighted
sum), so the realized weights still total one.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.agents.base import Agent
from repro.core.distributed import ShardedPrioritizedReplay
from repro.optim import compress
from repro.optim.collectives import fused_tree_reduce

Pytree = Any


def pmean_gradients(grads: Pytree, axes: Tuple[str, ...],
                    dtype=None) -> Pytree:
    """Shard-average the gradient pytree (psum / axis size).  The mean —
    not the raw sum — keeps the effective learning rate independent of
    the shard count.  ``dtype`` (e.g. ``jnp.bfloat16``) casts each leaf
    onto the wire before the reduce and back to its original dtype
    after — the bf16 intra-pod option, halving the reduce payload at the
    cost of mantissa bits (the injected error is surfaced per step as
    the ``compress_error_norm`` metric).  The whole pytree crosses the
    wire as ONE fused collective per axis (``optim/collectives.py``) —
    bit-exact against the per-leaf form, but a single launch on a real
    multi-process transport."""
    cast = dtype is not None and bool(axes)   # no axes → nothing on a wire
    wire = jax.tree.map(lambda g: g.astype(dtype), grads) if cast else grads
    red = fused_tree_reduce(wire, axes, jax.lax.pmean)
    if cast:
        red = jax.tree.map(lambda o, g: o.astype(g.dtype), red, grads)
    return red


def _pmean_inexact(tree: Pytree, axes: Tuple[str, ...]) -> Pytree:
    """pmean only float leaves (opt-state step counters stay int)."""
    return fused_tree_reduce(
        tree, axes, jax.lax.pmean,
        select=lambda x: jnp.issubdtype(x.dtype, jnp.inexact))


def _weighted_psum(tree: Pytree, scale: jax.Array, axes: Tuple[str, ...],
                   dtype=None) -> Pytree:
    """psum of ``leaf * scale`` over ``axes`` (scale is a per-shard
    scalar); ``dtype`` casts onto the wire like ``pmean_gradients``, and
    the reduce is fused the same way (one launch per axis)."""
    cast = dtype is not None and bool(axes)
    scaled = jax.tree.map(lambda x: x * scale, tree)
    if cast:
        scaled = jax.tree.map(lambda x: x.astype(dtype), scaled)
    red = fused_tree_reduce(scaled, axes, jax.lax.psum)
    if cast:
        red = jax.tree.map(lambda o, x: o.astype(x.dtype), red, tree)
    return red


def _renormalize(w: jax.Array, total: jax.Array) -> jax.Array:
    """``w / Σw`` with the all-stale clamp — the single renormalization
    used by both the production reduce (``total`` = psum over the mesh)
    and the property-testable vector form (``total`` = jnp.sum)."""
    return w / jnp.maximum(total, 1e-12)


def resolve_reduce_dtype(intra_pod_dtype: Optional[str]):
    """Map the executor-facing intra-pod reduce dtype option onto a jnp
    dtype (None = f32, no cast)."""
    if intra_pod_dtype in (None, "f32", "float32"):
        return None
    if intra_pod_dtype in ("bf16", "bfloat16"):
        return jnp.bfloat16
    raise ValueError(
        f"intra_pod_dtype={intra_pod_dtype!r}: expected 'f32' or 'bf16'")


def make_grad_reducer(
    axes: Tuple[str, ...],
    max_staleness: Optional[int] = None,
    compress_axis: Optional[str] = None,
    intra_pod_dtype: Optional[str] = None,
    overlap: bool = False,
):
    """Build the cross-shard gradient reduce used by ``sharded_learn``:
    ``reduce_grads(grads, age, ef) → (reduced, ef')`` over mesh ``axes``
    (call inside shard_map, or vmap with axis names in tests).

    Plain pmean by default; bounded-staleness renormalized weighted psum
    with ``max_staleness``; hierarchical f32-intra-pod / int8-EF-cross-
    pod with ``compress_axis`` (DESIGN.md §7) — composable with both.
    ``intra_pod_dtype='bf16'`` halves the wire payload of the fast-axis
    leg (all axes when there is no compressed pod leg) by casting each
    leaf to bf16 around the reduce.

    ``overlap=True`` double-buffers the compressed pod leg (DESIGN.md
    §10): learn event *i* applies this event's intra-pod partial plus
    the cross-pod *correction* computed at event *i−1*,

        applied_i = p_i + (pm_{i−1} − p_{i−1})

    so the slow ``compressed_pmean`` issued at event *i* is consumed
    only at event *i+1* — its result leaves the critical path and the
    collective runs concurrently with the next actor/learn chunk (XLA /
    the gloo transport overlap it with compute because nothing in this
    step's program depends on it).  The carried state becomes
    ``{"ef": …, "prev_mean": …, "prev_partial": …}``: the quantizer's EF
    buffer plus the previous event's pod mean and intra-pod partial.
    The update is computed as ``pm_{i−1} + (p_i − p_{i−1})`` — the same
    value, associated so that a constant gradient stream yields the
    barrier reduce's previous-event output *bit-exactly* from the second
    event on (the delta is exactly zero); for varying streams the
    cumulative difference telescopes to ``p_T − pm_T`` — one gradient's
    pod disagreement, never compounding (tests/test_distributed.py).
    Incompatible with ``max_staleness``: the staleness-weighted partial
    sums renormalize by a *global* total, which would need this event's
    cross-pod traffic on the critical path again.
    """
    if compress_axis is not None and compress_axis not in axes:
        raise ValueError(
            f"compress_axis={compress_axis!r} is not one of the mesh "
            f"axes {axes}")
    if overlap and compress_axis is None:
        raise ValueError(
            "overlap=True needs compress_axis: the double buffer defers "
            "the compressed cross-pod leg — with no pod leg there is "
            "nothing to overlap (the intra-pod pmean stays synchronous)")
    if overlap and max_staleness is not None:
        raise ValueError(
            "overlap=True is incompatible with max_staleness: the "
            "bounded-staleness reduce renormalizes by a global weight "
            "total, which puts this event's cross-pod traffic back on "
            "the critical path — pick one of the two staleness forms")
    fast_axes = tuple(ax for ax in axes if ax != compress_axis)
    wire_dtype = resolve_reduce_dtype(intra_pod_dtype)

    def reduce_grads(grads, age, ef):
        if compress_axis is not None and not jax.tree.leaves(ef):
            raise ValueError(
                "compress_axis is set but no error-feedback buffer was "
                "passed: thread LoopState.ef_error through the learn fn "
                "(init_loop_state(..., ef_buffer=True) materializes it)")
        if overlap:
            # double-buffered pod leg: apply the one-event-stale cross-
            # pod mean corrected by the fresh local delta, issue this
            # event's compressed mean for the next event.  pm + (p − p')
            # rather than p + (pm − p'): for an unchanged partial the
            # delta is exactly 0.0 and the applied update is bitwise the
            # previous barrier output.
            partial = pmean_gradients(grads, fast_axes, dtype=wire_dtype)
            pod_mean, new_ef = compress.compressed_pmean(
                partial, ef["ef"], compress_axis)
            applied = jax.tree.map(
                lambda pm, p, pp: pm + (p - pp),
                ef["prev_mean"], partial, ef["prev_partial"])
            return applied, {"ef": new_ef, "prev_mean": pod_mean,
                             "prev_partial": partial}
        if max_staleness is None or age is None:
            if compress_axis is None:
                return pmean_gradients(grads, axes, dtype=wire_dtype), ef
            # hierarchical: f32/bf16 mean inside the pod, int8-EF mean
            # across pods — equals the global pmean up to the wire error
            partial = pmean_gradients(grads, fast_axes, dtype=wire_dtype)
            return compress.compressed_pmean(partial, ef, compress_axis)
        w = staleness_weights(age, max_staleness)
        total = w
        for ax in axes:
            total = jax.lax.psum(total, ax)
        # renormalized weighted reduce: realized weight of shard d is
        # w_d / Σw — sums to 1 while any shard is within the bound, and
        # degrades to an all-zero gradient (params held) when none is
        wn = _renormalize(w, total)
        if compress_axis is None:
            return _weighted_psum(grads, wn, axes, dtype=wire_dtype), ef
        # weighted hierarchical reduce: f32 weighted partial sums inside
        # the pod, then the compressed mean across pods scaled by the
        # static pod count — mean × P = the cross-pod sum, so the
        # realized weights still total exactly 1.  An all-stale round
        # must degrade to an exactly-zero update with the EF buffer held:
        # the quantizer folds the carried error into zero partials, so
        # without the gate it would emit ≈ Σ_pods ef_p as a gradient.
        partial = _weighted_psum(grads, wn, fast_axes, dtype=wire_dtype)
        pod_mean, new_ef = compress.compressed_pmean(partial, ef,
                                                     compress_axis)
        n_pods = jax.lax.psum(1, compress_axis)
        alive = total > 0
        reduced = jax.tree.map(
            lambda g: jnp.where(alive, g * n_pods, 0.0), pod_mean)
        ef = jax.tree.map(lambda n, o: jnp.where(alive, n, o), new_ef, ef)
        return reduced, ef

    return reduce_grads


def make_sharded_learn(
    agent: Agent,
    replay: ShardedPrioritizedReplay,
    batch_per_shard: int,
    beta: float = 0.4,
    max_staleness: Optional[int] = None,
    compress_axis: Optional[str] = None,
    intra_pod_dtype: Optional[str] = None,
    lazy_writes: bool = False,
    overlap: bool = False,
):
    """Per-shard learner call: local PER sample → local grads → reduce →
    update (paper §V-B parameter-server adaptation).

    Returns ``sharded_learn(agent_state, replay_state, rng, age=None,
    ef=None) → (agent_state', replay_state', learn_metrics, ef')`` — the
    same signature as the fused ``make_learner_step`` (``learn_metrics``
    carries ``loss`` and ``compress_error_norm``) — to be invoked *inside*
    ``shard_map`` over ``replay.config.axis_names``:

      * the PER sample is local to the shard's tree/storage, with
        importance weights against the psum'd global distribution
        (``ShardedPrioritizedReplay.sample``);
      * agents exposing the ``grads``/``apply_grads`` split get the exact
        data-parallel reduction: grads are pmean'd across shards before
        the optimizer step, so replicated params stay bit-identical;
      * with ``compress_axis`` set (the 2-D pod×data mesh), the reduce is
        hierarchical: an f32 pmean over the remaining (fast intra-pod)
        axes, then the int8 error-feedback ``compressed_pmean`` across
        ``compress_axis`` — ``ef`` carries the per-shard EF buffer in
        and the contracted buffer out (``LoopState.ef_error``);
      * with ``max_staleness`` set (the async executor's sharded path),
        the pmean becomes the bounded-staleness weighted reduce: each
        shard's gradient is scaled by ``staleness_weights(age,
        max_staleness)`` and the psum renormalized by the total weight —
        a shard whose acting copy aged past the bound is dropped from
        the reduce and the surviving weights sum to one (``age`` is the
        shard's ``LoopState.params_age``).  Composed with
        ``compress_axis``, the weighted partials psum in f32 inside the
        pod and cross the pod axis as ``compressed_pmean × n_pods`` (the
        weighted sum, since the weights were renormalized globally);
      * agents without the split fall back to a local ``learn`` followed
        by a parameter/target/opt pmean (gossip-average; identical result
        at 1 shard, approximate beyond) — incompatible with
        ``compress_axis`` (there is no gradient pytree to compress) and
        with ``intra_pod_dtype`` (no gradient pytree to cast);
      * ``intra_pod_dtype='bf16'`` casts the fast-axis reduce leg to
        bf16 on the wire; the injected error is reported per learn as
        ``compress_error_norm`` (local cast error ‖g − bf16(g)‖₂,
        summed with the EF-buffer norm of the int8 pod leg when both
        compressions are active);
      * priority write-back stays local (write-after-read, §IV-D3);
        ``lazy_writes=True`` defers its propagation to the runtime
        loop's per-iteration flush (DESIGN.md §9);
      * ``overlap=True`` (requires ``compress_axis``) double-buffers the
        compressed pod leg — this learn applies the previous learn's
        cross-pod correction while issuing its own off the critical path
        (``make_grad_reducer``, DESIGN.md §10).  ``ef`` then carries the
        ``{"ef", "prev_mean", "prev_partial"}`` triple
        (``init_loop_state(..., overlap=True)``); only the ``"ef"``
        entry feeds the ``compress_error_norm`` metric, matching the
        barrier reduce.
    """
    axes = replay.config.axis_names
    if compress_axis is not None and (agent.grads is None
                                      or agent.apply_grads is None):
        raise ValueError(
            f"agent {agent.name!r} has no grads/apply_grads split: the "
            "compressed cross-pod reduce needs the explicit gradient "
            "pytree (the parameter-average fallback has nothing to "
            "quantize)")
    wire_dtype = resolve_reduce_dtype(intra_pod_dtype)
    if wire_dtype is not None and (agent.grads is None
                                   or agent.apply_grads is None):
        raise ValueError(
            f"agent {agent.name!r} has no grads/apply_grads split: the "
            "bf16 intra-pod reduce needs the explicit gradient pytree "
            "(the parameter-average fallback has nothing to cast)")
    # the cast only happens when a fast-axis reduce actually exists —
    # with every mesh axis consumed by the compressed pod leg there is
    # no intra-pod wire, so no cast and no cast-error metric
    fast_axes = tuple(ax for ax in axes if ax != compress_axis)
    cast_active = wire_dtype is not None and bool(fast_axes)
    reduce_grads = make_grad_reducer(axes, max_staleness=max_staleness,
                                     compress_axis=compress_axis,
                                     intra_pod_dtype=intra_pod_dtype,
                                     overlap=overlap)

    def sharded_learn(agent_state, replay_state, rng, age=None, ef=None):
        # phase scopes as in loop.make_learner_step (runtime/phases.py)
        with jax.named_scope("sample"):
            idx, items, is_w = replay.sample(replay_state, rng,
                                             batch_per_shard, beta)
        err_norm = jnp.zeros(())
        if agent.grads is not None and agent.apply_grads is not None:
            with jax.named_scope("learner_update"):
                grads, aux = agent.grads(agent_state, items, is_w)
                if cast_active:
                    # compression error this shard injects into the fast
                    # leg
                    err_norm = err_norm + compress.l2_norm(jax.tree.map(
                        lambda g: g - g.astype(wire_dtype).astype(g.dtype),
                        grads))
            with jax.named_scope("grad_reduce"):
                grads, ef = reduce_grads(grads, age, ef)
                if jax.tree.leaves(ef):
                    # residual the int8 pod leg carries into the next
                    # step (overlap mode also carries the stale
                    # correction — only the quantizer's EF half is
                    # compression error)
                    err_norm = err_norm + compress.l2_norm(
                        ef["ef"] if overlap else ef)
            with jax.named_scope("learner_update"):
                agent_state, metrics, td = agent.apply_grads(agent_state,
                                                             grads, aux)
        else:
            with jax.named_scope("learner_update"):
                agent_state, metrics, td = agent.learn(agent_state, items,
                                                       is_w)
            with jax.named_scope("grad_reduce"):
                agent_state = agent_state._replace(
                    params=_pmean_inexact(agent_state.params, axes),
                    target=_pmean_inexact(agent_state.target, axes),
                    opt=_pmean_inexact(agent_state.opt, axes),
                )
        with jax.named_scope("write_back"):
            replay_state = replay.update_priorities(replay_state, idx, td,
                                                    lazy=lazy_writes)
        lmetrics = {"loss": metrics["loss"], "compress_error_norm": err_norm}
        return agent_state, replay_state, lmetrics, ef

    return sharded_learn


def staleness_weights(ages: jax.Array, max_staleness: int) -> jax.Array:
    """Bounded-staleness discount: weight 1/(1+age), 0 beyond the bound
    (dropped straggler)."""
    w = 1.0 / (1.0 + ages.astype(jnp.float32))
    return jnp.where(ages > max_staleness, 0.0, w)


def staleness_reduce_weights(ages: jax.Array, max_staleness: int) -> jax.Array:
    """Realized per-shard reduce weights of the bounded-staleness reduce:
    ``staleness_weights`` renormalized by their sum over the shard vector.

    Invariant (property-tested): the weights sum to exactly the gradient
    scale of a synchronous pmean — 1 — whenever at least one shard is
    within the bound, and to 0 (update skipped, params held) when every
    shard is stale."""
    w = staleness_weights(ages, max_staleness)
    return _renormalize(w, jnp.sum(w))
