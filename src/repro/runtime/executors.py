"""Executor layer: one training-loop API, three runtime backends.

An executor owns the composed actor/learner step program (runtime/loop.py)
and drives it through chunked ``lax.scan``:

  * ``FusedExecutor``   — the single-jit path: all actors, the buffer and
    the learners live in one XLA program on the default device.  This is
    the paper's single-node regime (and the previous ``loop.train``).

  * ``ShardedExecutor`` — the whole step runs inside ``shard_map`` over
    the replay config's mesh axes: each shard owns E/D envs and one
    replay shard (``ShardedPrioritizedReplay``: local K-ary tree +
    storage), actors insert locally, learners sample locally with
    globally-corrected PER weights (one scalar psum), and gradients are
    pmean'd before the optimizer step
    (runtime/learner.make_sharded_learn) so the replicated agent state
    stays in lockstep.  This is the paper's parallel actors + parallel
    learners architecture mapped onto a device mesh (DESIGN.md §3).
    The mesh may be 1-D (``("data",)``) or 2-D pod-scale
    (``("pod", "data")`` via ``launch.mesh.pod_data_mesh``); on the 2-D
    mesh ``compress_pod_reduce=True`` switches the gradient reduce to
    the hierarchical form (DESIGN.md §7): f32 pmean over the fast
    intra-pod ``data`` axis, then the int8 error-feedback compressed
    mean (``optim/compress.compressed_pmean``) across the slow ``pod``
    links, with the EF buffer threaded through ``LoopState.ef_error``.

  * ``AsyncExecutor``   — the bounded-staleness path (DESIGN.md §5):
    actors act on a *delayed* parameter copy, double-buffered in
    ``LoopState.actor_params`` and republished from the fresh learner
    params every ``publish_interval`` iterations, while learners keep
    updating the fresh params — the paper's "actors never block on
    learners" decoupling (§IV-D) realized inside a deterministic program.
    Without a mesh it wraps the fused program; with a mesh the shard
    publish ticks are staggered and each shard's gradient contribution is
    scaled by ``staleness_weights(age, max_staleness)`` with the reduce
    weight renormalized — a shard past the bound is dropped from the
    reduce (runtime/learner.py).  At ``publish_interval=1,
    max_staleness=0`` it reproduces the synchronous executors
    trajectory-exactly (tests/test_async_executor.py).

All executors realize the same ``RatioSchedule``, so a 1-shard
``ShardedExecutor`` reproduces ``FusedExecutor`` metrics exactly from the
same seed (asserted in tests/test_executors.py), and ``Executor.run``
performs exactly the requested number of iterations (full chunks plus an
exact-length tail chunk, one cached jit per tail length).

Every chunk program **donates the replay state** (tree + storage) at the
jit boundary (``donate_argnums``): the multi-MB sum tree and transition
storage buffers are aliased input↔output instead of copied per chunk
call, completing the lazy-write story — one propagation pass per
iteration (runtime/loop.py) and zero surviving tree copies across the
scan/jit seam.  Callers must treat ``state.replay`` as consumed by
``run_chunk`` (use the returned state; the other LoopState fields —
agent params, the async double buffer, env state — are *not* donated, so
holding references to those across chunks stays legal).

Typical use::

    env_fn = functools.partial(make_vec, "cartpole")
    ex = FusedExecutor(agent, replay, env_fn, cfg, n_envs=8)
    state, history = ex.train(iterations=2000, key=jax.random.PRNGKey(0))

    mesh = data_mesh(4)
    srb = ShardedPrioritizedReplay(ShardedReplayConfig(...), example)
    ex = ShardedExecutor(agent, srb, env_fn, cfg, n_envs=8, mesh=mesh)
    state, history = ex.train(iterations=2000, key=jax.random.PRNGKey(0))

    ex = AsyncExecutor(agent, srb, env_fn, cfg, n_envs=8, mesh=mesh,
                       publish_interval=4, max_staleness=1)
    state, history = ex.train(iterations=2000, key=jax.random.PRNGKey(0))
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from repro.agents.base import Agent
from repro.core.distributed import ShardedPrioritizedReplay
from repro.core.replay import PrioritizedReplay
from repro.runtime import phases
from repro.runtime.learner import make_sharded_learn
from repro.runtime.loop import (METRIC_KEYS, LoopConfig, LoopState,
                                RatioSchedule, init_loop_state, make_step)

Pytree = Any


class Executor:
    """Common chunked-scan driver; subclasses provide init() and
    _build_chunk(length)."""

    schedule: RatioSchedule
    scan_chunk: int
    chunks_dispatched: int = 0    # run_chunk calls, the span's step_num

    def init(self, key: jax.Array) -> LoopState:
        raise NotImplementedError

    def _build_chunk(self, length: int) -> Callable:
        """Compile (state) → (state, per-iteration metrics of shape
        (length,)) scanning the step ``length`` times."""
        raise NotImplementedError

    def run_chunk(self, state: LoopState, length: Optional[int] = None):
        """(state) → (state, per-iteration metrics of shape (length,)).

        Compiled programs are cached per distinct ``length`` — the run
        loop only ever uses ``scan_chunk`` plus one tail length.  The
        host's dispatch is one profiler step, ``run_chunk`` with
        ``step_num`` (chunks this executor dispatched before) and
        ``length`` (iterations): the program's single chunk marker."""
        length = self.scan_chunk if length is None else length
        step_num = self.chunks_dispatched
        self.chunks_dispatched = step_num + 1
        with jax.profiler.StepTraceAnnotation("run_chunk", step_num=step_num,
                                              length=length):
            return self._chunk(length)(state)

    def lower_chunk(self, state: LoopState):
        """The ``scan_chunk``-long program ``run_chunk`` runs, lowered
        for ``state`` (``.compile().as_text()`` shows which kernels it
        holds); the arrays are not consumed."""
        return self._chunk(None).lower(state)

    def op_phases(self, state: LoopState) -> Dict[str, str]:
        """``{HLO instruction name: phase}`` of the compiled chunk that
        ``run_chunk`` runs for ``state`` (arrays or ShapeDtypeStructs;
        nothing is consumed).  A profile names each device op by its
        instruction, so this joins a profile's op times to the step's
        phases (runtime/phases.py); ops it lacks belong to no phase."""
        return phases.op_phases(self.lower_chunk(state).compile().as_text())

    def _chunk(self, length: Optional[int]) -> Callable:
        length = self.scan_chunk if length is None else length
        cache = getattr(self, "_chunks", None)
        if cache is None:
            cache = self._chunks = {}
        fn = cache.get(length)
        if fn is None:
            fn = cache[length] = self._build_chunk(length)
        return fn

    def run(self, state: LoopState, iterations: int, log_every: int = 0
            ) -> Tuple[LoopState, Dict[str, jax.Array]]:
        """Run *exactly* ``iterations`` iterations: full ``scan_chunk``
        chunks plus one exact-length tail chunk (no off-by-chunk
        overshoot).  ``history`` holds the last iteration's metrics of
        each chunk."""
        if iterations < 1:
            raise ValueError(f"iterations={iterations}: need ≥ 1")
        history = []
        done_iters = 0
        while done_iters < iterations:
            length = min(self.scan_chunk, iterations - done_iters)
            state, metrics = self.run_chunk(state, length)
            prev_iters, done_iters = done_iters, done_iters + length
            last = jax.tree.map(lambda x: x[-1], metrics)
            history.append(last)
            if log_every and done_iters // log_every > prev_iters // log_every:
                print(f"iter={done_iters} "
                      f"return={float(last['mean_episode_return']):.1f} "
                      f"loss={float(last['loss']):.4f} "
                      f"buffer={int(last['buffer_size'])} "
                      f"learns={int(last['learn_steps'])}")
        return state, jax.tree.map(lambda *xs: jnp.stack(xs), *history)

    def train(self, iterations: int, key: jax.Array, log_every: int = 0
              ) -> Tuple[LoopState, Dict[str, jax.Array]]:
        return self.run(self.init(key), iterations, log_every)


class FusedExecutor(Executor):
    """Single-jit fused path (the paper's single-node regime).

    ``publish_interval`` is plumbing for ``AsyncExecutor``: > 0 switches
    the step into double-buffered acting (actors read the delayed
    ``actor_params`` copy, republished every ``publish_interval``
    iterations); 0 (the default) is the synchronous loop.
    ``external_publish=True`` removes the in-program republish — the
    host runtime rewrites ``actor_params`` between chunks via a real
    device→host→device transfer (``launch/multiprocess.py``)."""

    def __init__(
        self,
        agent: Agent,
        replay: PrioritizedReplay,
        env_fn: Callable[[int], tuple],
        cfg: LoopConfig,
        n_envs: int,
        scan_chunk: int = 64,
        publish_interval: int = 0,
        external_publish: bool = False,
    ):
        self.agent = agent
        self.replay = replay
        self.cfg = cfg
        self.n_envs = n_envs
        self.scan_chunk = scan_chunk
        self.publish_interval = publish_interval
        self.external_publish = external_publish
        self._chunks: Dict[int, Callable] = {}
        self.spec, self._v_reset, self._v_step = env_fn(n_envs)
        self.schedule = RatioSchedule.from_config(cfg, n_envs)
        self.step = make_step(agent, replay, self._v_step, cfg, n_envs,
                              schedule=self.schedule,
                              publish_interval=publish_interval,
                              external_publish=external_publish)

    def _build_chunk(self, length: int) -> Callable:
        def chunk(replay_state, rest):
            state = rest._replace(replay=replay_state)

            def body(s, _):
                return self.step(s)
            return jax.lax.scan(body, state, None, length=length)

        # tree + storage are donated: XLA aliases the replay buffers
        # input↔output instead of round-tripping a copy per chunk
        fn = jax.jit(chunk, donate_argnums=(0,))

        def run(state: LoopState):
            return fn(state.replay, state._replace(replay=()))
        run.lower = lambda state: fn.lower(state.replay,
                                           state._replace(replay=()))
        return run

    def init(self, key: jax.Array) -> LoopState:
        return init_loop_state(self.agent, self.replay, self._v_reset, key,
                               self.n_envs,
                               double_buffer=self.publish_interval > 0)


class ShardedExecutor(Executor):
    """shard_map path: per-shard actors + replay shard, pmean'd learners.

    ``n_envs`` is the *global* env count; each of the mesh's D shards
    (D = the product of the replay config's axis extents — e.g. a 2×2
    pod×data mesh has D=4) runs ``n_envs / D`` envs and holds one replay
    shard.  The learner batch is ``cfg.batch_size / D`` per shard
    (global batch preserved under the gradient pmean).  Shard identity
    is the *flattened* (pod, data) index — row-major over
    ``replay.config.axis_names`` — so a 2×1 pod×data mesh reproduces a
    1-D 2-shard data mesh exactly (same rng folds, same stagger phases).

    ``compress_pod_reduce=True`` (2-D meshes only — the first axis is
    the slow inter-pod one) swaps the cross-pod leg of the gradient
    reduce for the int8 error-feedback compressed mean; the per-shard EF
    buffer rides in ``LoopState.ef_error`` with the same leading-shard-
    axis layout as the replay shards.  ``overlap_pod_reduce=True`` (on
    top of ``compress_pod_reduce``) double-buffers that compressed pod
    leg: each learn applies the previous learn's cross-pod correction
    while its own ``compressed_pmean`` runs off the critical path
    (``make_grad_reducer(overlap=True)``, DESIGN.md §10); ``ef_error``
    then carries the per-shard ``{"ef", "prev_mean", "prev_partial"}``
    triple.

    ``publish_interval``/``max_staleness`` are plumbing for
    ``AsyncExecutor``: with ``publish_interval > 0`` each shard acts on
    its own delayed parameter copy (publish ticks staggered by shard id,
    so shard ages differ) and the gradient pmean becomes the bounded-
    staleness renormalized reduce of ``runtime/learner.py``.
    """

    def __init__(
        self,
        agent: Agent,
        replay: ShardedPrioritizedReplay,
        env_fn: Callable[[int], tuple],
        cfg: LoopConfig,
        n_envs: int,
        mesh: Mesh,
        scan_chunk: int = 64,
        publish_interval: int = 0,
        max_staleness: Optional[int] = None,
        compress_pod_reduce: bool = False,
        intra_pod_dtype: Optional[str] = None,
        overlap_pod_reduce: bool = False,
        external_publish: bool = False,
    ):
        axes = tuple(replay.config.axis_names)
        missing = [ax for ax in axes if ax not in mesh.shape]
        if missing:
            raise ValueError(f"replay axes {missing} not in mesh axes "
                             f"{tuple(mesh.shape)}")
        extra = [ax for ax in mesh.shape if ax not in axes]
        if extra:
            raise ValueError(
                f"mesh axes {extra} are not in the replay config's "
                f"axis_names {axes}: the executor would replicate every "
                "shard across them (duplicate programs on "
                f"{math.prod(mesh.shape[ax] for ax in extra)}× the "
                "devices, no extra capacity or gradient averaging) — "
                "name every mesh axis in ShardedReplayConfig.axis_names, "
                "e.g. axis_names=(\"pod\", \"data\") for pod_data_mesh")
        if compress_pod_reduce and len(axes) < 2:
            raise ValueError(
                "compress_pod_reduce needs a multi-axis (pod, data) mesh: "
                f"with the single axis {axes} there is no slow cross-pod "
                "link to compress — the intra-pod reduce stays f32")
        if overlap_pod_reduce and not compress_pod_reduce:
            raise ValueError(
                "overlap_pod_reduce needs compress_pod_reduce=True: the "
                "double buffer defers the *compressed* cross-pod leg — "
                "there is no overlapped form of the plain global pmean")
        if overlap_pod_reduce and publish_interval and max_staleness is not None:
            raise ValueError(
                "overlap_pod_reduce is incompatible with max_staleness: "
                "the bounded-staleness reduce renormalizes by a global "
                "weight total, which puts this event's cross-pod traffic "
                "back on the critical path (runtime/learner.py)")
        self._axes = axes
        axis_sizes = tuple(mesh.shape[ax] for ax in axes)
        n_shards = math.prod(axis_sizes)
        if n_envs % n_shards:
            raise ValueError(f"n_envs={n_envs} not divisible by "
                             f"{n_shards} shards")
        if cfg.batch_size % n_shards:
            raise ValueError(f"batch_size={cfg.batch_size} not divisible by "
                             f"{n_shards} shards")
        self.agent = agent
        self.replay = replay
        self.cfg = cfg
        self.mesh = mesh
        self.n_shards = n_shards
        self.n_envs = n_envs
        self.n_envs_local = n_envs // n_shards
        self.scan_chunk = scan_chunk
        self.publish_interval = publish_interval
        self.max_staleness = max_staleness
        self.compress_pod_reduce = compress_pod_reduce
        self.intra_pod_dtype = intra_pod_dtype
        self.overlap_pod_reduce = overlap_pod_reduce
        self.external_publish = external_publish
        self._chunks: Dict[int, Callable] = {}
        self.spec, self._v_reset, self._v_step = env_fn(self.n_envs_local)
        self.schedule = RatioSchedule.from_config(cfg, n_envs)

        if publish_interval and max_staleness is not None:
            # the staggered publish clock of shard d has fixed phase d mod
            # P, so at learn ticks (every `period` iterations) its age
            # cycles over {(d + k·gcd(P, period)) mod P} with minimum
            # d mod gcd — a shard whose minimum exceeds the bound would be
            # dropped from EVERY reduce and its replay data never trains
            g = math.gcd(publish_interval, self.schedule.period)
            if min(g, n_shards) > max_staleness + 1:
                raise ValueError(
                    f"publish_interval={publish_interval} and the learn "
                    f"period {self.schedule.period} share the factor {g} > "
                    f"max_staleness+1={max_staleness + 1}: shards whose "
                    "staggered publish phase exceeds the staleness bound at "
                    "every learn tick would be permanently dropped from the "
                    "gradient reduce (their replay data would never train). "
                    "Pick a publish_interval coprime with the learn period "
                    "or raise max_staleness.")

        learn_fn = make_sharded_learn(
            agent, replay, batch_per_shard=cfg.batch_size // n_shards,
            beta=cfg.beta,
            max_staleness=max_staleness if publish_interval else None,
            compress_axis=axes[0] if compress_pod_reduce else None,
            intra_pod_dtype=intra_pod_dtype,
            lazy_writes=cfg.lazy_replay,
            overlap=overlap_pod_reduce)

        def flat_shard_id():
            # row-major flattened (pod, data) index over the mesh axes —
            # the single integer identity used for rng folds and the
            # staggered publish clocks
            sid = jnp.zeros((), jnp.int32)
            for ax, size in zip(axes, axis_sizes):
                sid = sid * size + jax.lax.axis_index(ax)
            return sid

        # metric reduction deliberately does NOT ride the per-iteration
        # step (identity mean_across/sum_across): the scanned step emits
        # shard-local metrics and _reduce_metrics contracts the whole
        # chunk's stack with one fused collective per chunk — on the
        # real multi-process transport the 7-per-iteration metric
        # collectives were most of the wall-clock (DESIGN.md §10)
        self.step = make_step(
            agent, replay, self._v_step, cfg, self.n_envs_local,
            schedule=self.schedule,
            learn_fn=learn_fn,
            shard_id=flat_shard_id,
            publish_interval=publish_interval,
            external_publish=external_publish,
        )

        self._specs = self._state_specs()
        self._metric_specs = {k: PartitionSpec() for k in METRIC_KEYS}

        def init_local(key):
            st = init_loop_state(agent, replay, self._v_reset, key,
                                 self.n_envs_local, shard_id=flat_shard_id(),
                                 double_buffer=publish_interval > 0,
                                 ef_buffer=compress_pod_reduce,
                                 overlap=overlap_pod_reduce)
            return self._global_state(st)

        self._init = jax.jit(jax.shard_map(
            init_local, mesh=mesh, in_specs=(PartitionSpec(),),
            out_specs=self._specs, check_vma=False))

    def _reduce_metrics(self, metrics: Dict[str, jax.Array]
                        ) -> Dict[str, jax.Array]:
        """Contract the chunk's stacked shard-local metrics across the
        mesh in ONE fused collective (call inside shard_map, after the
        scan).  The per-iteration form reduced 7 scalars per step — at
        real multi-process launch latencies that was most of the
        wall-clock budget; here the cross-shard keys of the whole
        (length,)-stacked chunk share a single pmean.  ``buffer_size``
        rides the same f32 pmean as mean × shard count: counts are ≤
        capacity (exact in f32) and the round() clears the /D·D
        rounding when the shard count is not a power of two.  Values
        are bit-identical to the per-iteration reduction — psum
        commutes with stacking."""
        stack = jnp.stack([
            metrics["loss"],
            metrics["mean_episode_return"],
            metrics["compress_error_norm"],
            metrics["buffer_size"].astype(jnp.float32),
        ])
        for ax in self._axes:
            stack = jax.lax.pmean(stack, ax)
        out = dict(metrics)
        out["loss"] = stack[0]
        out["mean_episode_return"] = stack[1]
        out["compress_error_norm"] = stack[2]
        out["buffer_size"] = jnp.round(stack[3] * self.n_shards).astype(
            metrics["buffer_size"].dtype)
        return out

    def _build_chunk(self, length: int) -> Callable:
        def chunk_local(replay_g, rest_g):
            state = self._local_state(rest_g._replace(replay=replay_g))

            def body(s, _):
                return self.step(s)

            state, metrics = jax.lax.scan(body, state, None, length=length)
            return self._global_state(state), self._reduce_metrics(metrics)

        # replay (tree + storage) donated at the jit boundary, same as
        # the fused path — per-shard buffers alias through shard_map
        fn = jax.jit(jax.shard_map(
            chunk_local, mesh=self.mesh,
            in_specs=(self._specs.replay, self._specs._replace(replay=())),
            out_specs=(self._specs, self._metric_specs), check_vma=False),
            donate_argnums=(0,))

        def run(state: LoopState):
            return fn(state.replay, state._replace(replay=()))
        run.lower = lambda state: fn.lower(state.replay,
                                           state._replace(replay=()))
        return run

    # -- per-shard ↔ global state layout ----------------------------------
    #
    # Replay-shard leaves (tree, storage, head, count, max_priority) gain a
    # leading shard axis in the global representation: local (…) ↔ global
    # (D, …), so rank-0 per-shard scalars stay addressable under a
    # PartitionSpec(axes) without replication lies (on a 2-D mesh the
    # leading dim is sharded over BOTH axes — P(("pod", "data")) — in the
    # same row-major order as the flattened shard id).  The async double
    # buffer (actor_params, params_age) and the EF error buffer are laid
    # out the same way — each shard holds its *own* delayed copy / error
    # state (within a pod the EF copies are numerically identical, across
    # pods they differ).  Env-side leaves already carry the env axis,
    # which concatenates across shards to the global env count.  Agent
    # params / rng / counters are replicated.

    def _map_sharded_fields(self, state: LoopState, fn) -> LoopState:
        updates = {"replay": jax.tree.map(fn, state.replay)}
        if self.publish_interval:
            updates["actor_params"] = jax.tree.map(fn, state.actor_params)
            updates["params_age"] = fn(state.params_age)
        if self.compress_pod_reduce:
            updates["ef_error"] = jax.tree.map(fn, state.ef_error)
        return state._replace(**updates)

    def _local_state(self, gstate: LoopState) -> LoopState:
        return self._map_sharded_fields(gstate, lambda x: x[0])

    def _global_state(self, state: LoopState) -> LoopState:
        return self._map_sharded_fields(state, lambda x: x[None])

    def _state_specs(self) -> LoopState:
        key_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
        shapes = jax.eval_shape(
            lambda k: init_loop_state(self.agent, self.replay, self._v_reset,
                                      k, self.n_envs_local,
                                      double_buffer=self.publish_interval > 0,
                                      ef_buffer=self.compress_pod_reduce,
                                      overlap=self.overlap_pod_reduce),
            key_shape)
        # leading dim sharded over ALL mesh axes at once (row-major):
        # P(("pod", "data")) on the 2-D mesh, P(("data",)) ≡ P("data") 1-D
        dim0 = PartitionSpec(self._axes)
        rep = lambda tree: jax.tree.map(lambda _: PartitionSpec(), tree)
        shard = lambda tree: jax.tree.map(lambda _: dim0, tree)
        return LoopState(
            agent=rep(shapes.agent),
            replay=shard(shapes.replay),
            env_state=shard(shapes.env_state),
            obs=dim0,
            rng=PartitionSpec(),
            env_steps=PartitionSpec(),
            episode_return=dim0,
            last_return=dim0,
            learn_steps=PartitionSpec(),
            actor_params=shard(shapes.actor_params),
            params_age=shard(shapes.params_age),
            ef_error=shard(shapes.ef_error),
        )

    def init(self, key: jax.Array) -> LoopState:
        return self._init(key)


class AsyncExecutor(Executor):
    """Bounded-staleness backend (DESIGN.md §5): decoupled actor/learner
    parameter clocks.

    Actors act on a delayed copy of the agent params
    (``LoopState.actor_params``), republished from the fresh learner
    params every ``publish_interval`` iterations; learners update the
    fresh params every scheduled learn event.  Without ``mesh`` this
    wraps the fused program (``max_staleness`` is inert — there is no
    cross-shard reduce to weight).  With ``mesh`` the publish ticks are
    staggered per shard, so shards act at different parameter ages, and
    each shard's gradient enters the reduce scaled by
    ``staleness_weights(age, max_staleness)`` with the total weight
    renormalized — a shard past the bound is dropped, the survivors'
    realized weights sum to 1 (``runtime/learner.py``).

    At the identity settings ``publish_interval=1, max_staleness=0`` the
    delayed copy is republished every iteration and this executor
    reproduces the synchronous ones trajectory-exactly from the same
    seed (asserted in tests/test_async_executor.py).
    """

    def __init__(
        self,
        agent: Agent,
        replay,
        env_fn: Callable[[int], tuple],
        cfg: LoopConfig,
        n_envs: int,
        publish_interval: int = 1,
        max_staleness: int = 0,
        mesh: Optional[Mesh] = None,
        scan_chunk: int = 64,
        compress_pod_reduce: bool = False,
        intra_pod_dtype: Optional[str] = None,
        overlap_pod_reduce: bool = False,
        external_publish: bool = False,
    ):
        if publish_interval < 1:
            raise ValueError(
                f"publish_interval={publish_interval}: need ≥ 1 (1 = "
                "republish every iteration = the synchronous loop)")
        if max_staleness < 0:
            raise ValueError(f"max_staleness={max_staleness}: need ≥ 0")
        if overlap_pod_reduce and max_staleness:
            raise ValueError(
                "overlap_pod_reduce is incompatible with max_staleness > "
                "0: the bounded-staleness reduce renormalizes by a global "
                "weight total, putting this event's cross-pod traffic "
                "back on the critical path (runtime/learner.py)")
        if mesh is None:
            if compress_pod_reduce:
                raise ValueError(
                    "compress_pod_reduce needs a (pod, data) mesh — the "
                    "fused path has no cross-pod reduce to compress")
            if overlap_pod_reduce:
                raise ValueError(
                    "overlap_pod_reduce needs a (pod, data) mesh — the "
                    "fused path has no cross-pod reduce to overlap")
            if intra_pod_dtype not in (None, "f32", "float32"):
                raise ValueError(
                    "intra_pod_dtype needs a mesh — the fused path has "
                    "no cross-shard reduce to cast")
            self._impl: Executor = FusedExecutor(
                agent, replay, env_fn, cfg, n_envs, scan_chunk=scan_chunk,
                publish_interval=publish_interval,
                external_publish=external_publish)
        else:
            self._impl = ShardedExecutor(
                agent, replay, env_fn, cfg, n_envs, mesh,
                scan_chunk=scan_chunk, publish_interval=publish_interval,
                max_staleness=None if overlap_pod_reduce else max_staleness,
                compress_pod_reduce=compress_pod_reduce,
                intra_pod_dtype=intra_pod_dtype,
                overlap_pod_reduce=overlap_pod_reduce,
                external_publish=external_publish)
            self.n_shards = self._impl.n_shards
            self.n_envs_local = self._impl.n_envs_local
        self.agent = agent
        self.replay = replay
        self.cfg = cfg
        self.mesh = mesh
        self.n_envs = n_envs
        self.scan_chunk = scan_chunk
        self.publish_interval = publish_interval
        self.max_staleness = max_staleness
        self.compress_pod_reduce = compress_pod_reduce
        self.intra_pod_dtype = intra_pod_dtype
        self.overlap_pod_reduce = overlap_pod_reduce
        self.external_publish = external_publish
        self.spec = self._impl.spec
        self.step = self._impl.step
        self.schedule = self._impl.schedule

    def _build_chunk(self, length: int) -> Callable:
        return self._impl._build_chunk(length)

    def init(self, key: jax.Array) -> LoopState:
        return self._impl.init(key)


def executor_from_plan(
    plan,
    agent: Agent,
    env_fn: Callable[[int], tuple],
    cfg,
    example: Pytree,
    *,
    capacity: int = 50_000,
    fanout: int = 128,
    tree_backend: str = "xla",
    scan_chunk: int = 64,
    intra_pod_dtype: Optional[str] = None,
) -> Executor:
    """Instantiate the executor a ``runtime.planner.PlannedConfig``
    selected: the right backend class, mesh (``launch.mesh.
    mesh_from_plan``), replay flavor and async knobs, with the plan's
    ``n_envs`` and ``update_interval`` applied (the latter overrides
    ``cfg.update_interval`` — the plan *is* the Eq. 5 answer for the
    ratio it was solved at).

    The caller must have forced ``plan.n_devices`` host devices before
    the first jax call (``--xla_force_host_platform_device_count``);
    ``examples/quickstart.py --plan`` shows the full dance.

    A plan with ``n_replay_shards ≥ 1`` (the replay-service degrees of
    freedom, runtime/planner.py) routes experience through an in-process
    ``ReplayService`` behind a ``RateLimiter`` pinned to the plan's
    ``samples_per_insert`` — the ``ServiceExecutor`` form of the same
    workload (DESIGN.md §11).  Service plans run the fused (no-mesh)
    program per process; the multi-process service gang is launched by
    ``launch.multiprocess.launch_service`` instead.
    """
    import dataclasses as _dc

    from repro.core.distributed import ShardedReplayConfig
    from repro.launch.mesh import mesh_from_plan

    cfg = _dc.replace(cfg, update_interval=plan.update_interval)
    n_replay_shards = getattr(plan, "n_replay_shards", 0)
    if n_replay_shards:
        from repro.service.executor import ServiceExecutor
        from repro.service.rate_limiter import RateLimiter
        from repro.service.server import ReplayService, ReplayServiceConfig

        if mesh_from_plan(plan) is not None:
            raise ValueError(
                f"plan ({plan.describe()}) combines a device mesh with a "
                "replay service — the service executor runs the fused "
                "per-process program; use launch_service for a gang")
        service = ReplayService(
            ReplayServiceConfig(
                capacity_per_shard=max(1, capacity // n_replay_shards),
                n_shards=n_replay_shards, fanout=fanout,
                backend=tree_backend, router="round_robin"),
            example)
        limiter = None
        if plan.samples_per_insert:
            limiter = RateLimiter.for_loop(
                cfg.batch_size,
                max(1, round(cfg.batch_size / plan.samples_per_insert)),
                cfg.warmup, insert_burst=plan.n_envs)
        return ServiceExecutor(agent, service, env_fn, cfg, plan.n_envs,
                               scan_chunk=scan_chunk,
                               rate_limiter=limiter)
    mesh = mesh_from_plan(plan)
    if mesh is None:
        if intra_pod_dtype not in (None, "f32", "float32"):
            raise ValueError(
                f"intra_pod_dtype={intra_pod_dtype!r} but the plan "
                f"({plan.describe()}) runs the fused program — there is "
                "no cross-shard reduce to cast")
        from repro.core.replay import ReplayConfig
        replay = PrioritizedReplay(
            ReplayConfig(capacity=capacity, fanout=fanout,
                         backend=tree_backend), example)
        if plan.backend == "async":
            return AsyncExecutor(agent, replay, env_fn, cfg, plan.n_envs,
                                 publish_interval=plan.publish_interval,
                                 max_staleness=plan.max_staleness,
                                 scan_chunk=scan_chunk)
        return FusedExecutor(agent, replay, env_fn, cfg, plan.n_envs,
                             scan_chunk=scan_chunk)
    axis_names = ("pod", "data") if plan.n_pods > 1 else ("data",)
    replay = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=capacity // plan.n_shards,
                            fanout=fanout, backend=tree_backend,
                            axis_names=axis_names), example)
    overlap = getattr(plan, "overlap_pod_reduce", False)
    if plan.backend == "async":
        return AsyncExecutor(agent, replay, env_fn, cfg, plan.n_envs,
                             publish_interval=plan.publish_interval,
                             max_staleness=plan.max_staleness, mesh=mesh,
                             scan_chunk=scan_chunk,
                             compress_pod_reduce=plan.compress_pod_reduce,
                             intra_pod_dtype=intra_pod_dtype,
                             overlap_pod_reduce=overlap)
    return ShardedExecutor(agent, replay, env_fn, cfg, plan.n_envs, mesh,
                           scan_chunk=scan_chunk,
                           compress_pod_reduce=plan.compress_pod_reduce,
                           intra_pod_dtype=intra_pod_dtype,
                           overlap_pod_reduce=overlap)
