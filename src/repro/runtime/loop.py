"""The paper's training loop (Alg. 1) as composable actor/learner programs.

The fused iteration realizes lazy writing (§IV-D) as a replay
*transaction* (DESIGN.md §9): every tree mutation inside one iteration
writes only the sum tree's leaf level, and a single merged propagation
pass (``replay.flush``) runs at the sample boundary:

    1. ACTORS   — ε-greedy act on E vectorized envs, env step           (§V-A)
    2. INSERT-BEGIN — zero in-flight slot priorities (leaf-only write)
    3. FLUSH    — ONE upward propagation pass coalescing the previous
                  iteration's priority updates + insert-commit with this
                  iteration's insert-begin (lazy ≡ eager bit-exact here)
    4. LEARNERS — sample B from the flushed tree, TD update             (§V-B)
    5. PRIORITY UPDATE — leaf-only write, write-after-read tolerated  (§IV-D3)
    6. INSERT-COMMIT — storage write + P_max restore (leaf-only write)

Steps 5/6 defer their propagation to the *next* iteration's flush, so
the eager path's three full propagation passes per iteration collapse
to one (asserted by an op-count trace test).  Step 4 never depends on
step 6's storage write (in-flight slots are invisible by construction),
so XLA schedules the transition DMA concurrently with learner compute —
the same overlap the paper's lock split buys on a multicore CPU.
``LoopConfig.lazy_replay=False`` restores the eager per-op propagation
(the replay microbenchmark's baseline arm).

The loop is built from three pieces (DESIGN.md §3):

  * ``make_actor_step``   — one vectorized env interaction producing a
    batch of transitions (the paper's parallel actors);
  * ``make_learner_step`` — one PER sample → TD update → priority
    write-back (the paper's parallel learners);
  * ``RatioSchedule``     — the collection/consumption ratio.  The
    paper's ``update_interval`` (env steps per learn) is *honored*: with
    E envs per iteration and ratio U, the schedule runs round(E/U)
    learner calls per iteration (U < E) or one learner call every
    round(U/E) iterations (U ≥ E).  ``learns_per_step`` multiplies the
    learner calls per event, so both "N actor steps per learn" and
    "M learns per actor step" are expressible.

``make_step`` composes them into one jit-able program; the executors in
``runtime/executors.py`` run that program fused on one device, inside
``shard_map`` over a mesh data axis, or asynchronously: with
``publish_interval > 0`` the actors act on a *delayed* parameter copy
(``LoopState.actor_params``, double-buffered and republished from the
fresh learner params every ``publish_interval`` iterations, staggered by
shard id) while learners keep updating the fresh ``LoopState.agent`` —
the paper's "actors never block on learners" decoupling (§IV-D), with
``LoopState.params_age`` counting iterations since the last publish so
the sharded reduce can weight shards by staleness
(runtime/learner.staleness_weights).  ``publish_interval=1`` republishes
after every iteration, which is exactly the synchronous loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.agents.base import Agent, AgentState
from repro.core.replay import PrioritizedReplay, ReplayState
from repro.optim import compress

Pytree = Any

# keys of the metrics dict every composed step returns (make_step below);
# the sharded executor derives its shard_map out_specs from this tuple
METRIC_KEYS = ("loss", "mean_episode_return", "env_steps", "learn_steps",
               "buffer_size", "epsilon", "compress_error_norm")

# keys of the per-learn metrics dict every learn fn returns (the shared
# contract of make_learner_step and runtime/learner.make_sharded_learn)
LEARN_METRIC_KEYS = ("loss", "compress_error_norm")


class LoopState(NamedTuple):
    agent: AgentState
    replay: ReplayState
    env_state: Pytree
    obs: jax.Array
    rng: jax.Array
    env_steps: jax.Array
    episode_return: jax.Array     # running per-env return accumulator
    last_return: jax.Array        # most recently finished episode returns
    learn_steps: jax.Array        # cumulative learner update count
    # async double buffer (empty pytrees on the synchronous executors):
    actor_params: Pytree = ()     # delayed acting copy of the agent params
    params_age: Pytree = ()       # int32 iterations since the last publish
    # error-feedback buffer of the int8 cross-pod compressed reduce
    # (runtime/learner.py); an empty pytree whenever the executor's
    # reduce is uncompressed — 1-D meshes, fused, and plain sharded runs
    ef_error: Pytree = ()


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    batch_size: int = 128
    update_interval: int = 1      # env steps per learn step (paper ratio)
    learns_per_step: int = 1      # extra learner calls per learn event
    warmup: int = 1000            # env steps before learning starts
    epsilon: float = 0.1          # exploration at step 0
    epsilon_final: float = 0.02   # exploration floor after decay
    epsilon_decay_steps: int = 10_000   # env steps of linear ε decay
    beta: float = 0.4             # PER importance exponent
    lazy_replay: bool = True      # lazy-writing replay transactions: one
                                  # merged tree-propagation pass per
                                  # iteration (False = eager per-op passes)


@dataclasses.dataclass(frozen=True)
class RatioSchedule:
    """Static actor/learner interleave realizing ``update_interval``.

    ``period`` iterations separate learn events; each event runs
    ``learns`` learner calls.  Realized ratio (env steps per learn) is
    ``period * env_steps_per_iter / learns``.
    """

    period: int               # iterations between learn events (≥ 1)
    learns: int               # learner calls per event (≥ 1)
    env_steps_per_iter: int   # global env steps added per iteration

    @property
    def realized_ratio(self) -> float:
        return self.period * self.env_steps_per_iter / self.learns

    @classmethod
    def from_config(cls, cfg: LoopConfig, env_steps_per_iter: int) -> "RatioSchedule":
        u = max(1, cfg.update_interval)
        e = env_steps_per_iter
        if u >= e:
            return cls(period=max(1, round(u / e)),
                       learns=max(1, cfg.learns_per_step),
                       env_steps_per_iter=e)
        return cls(period=1,
                   learns=max(1, round(e / u)) * max(1, cfg.learns_per_step),
                   env_steps_per_iter=e)


def epsilon_schedule(cfg: LoopConfig, env_steps: jax.Array) -> jax.Array:
    """Linear ε decay: cfg.epsilon → cfg.epsilon_final over decay_steps."""
    frac = jnp.clip(
        env_steps.astype(jnp.float32) / max(1, cfg.epsilon_decay_steps), 0.0, 1.0
    )
    return cfg.epsilon + (cfg.epsilon_final - cfg.epsilon) * frac


# -- actor program -----------------------------------------------------------


def make_actor_step(agent: Agent, v_step: Callable, n_envs: int):
    """One parallel-actor interaction: act on E envs, step, package the
    transition batch (no weight mutation → no sync; paper §V-A)."""

    def actor_step(agent_state, env_state, obs, ep_ret, last_ret,
                   k_act, k_env, epsilon):
        actions = agent.act(agent_state, obs, k_act, epsilon)
        env_state, obs_next, rew, done, true_next = v_step(env_state, actions, k_env)
        ep_ret = ep_ret + rew
        last_ret = jnp.where(done, ep_ret, last_ret)
        ep_ret = jnp.where(done, 0.0, ep_ret)
        transitions = {
            "obs": obs,
            "action": actions,
            "reward": rew,
            "next_obs": true_next,
            "done": done.astype(jnp.float32),
        }
        return env_state, obs_next, ep_ret, last_ret, transitions

    return actor_step


# -- actor-side program (service boundary, DESIGN.md §11) --------------------


class ActorSlice(NamedTuple):
    """The actor-side state of the decoupled runtime: everything an actor
    fleet process owns when the replay buffer lives behind a service —
    env state plus the episode-return bookkeeping.  The agent params
    arrive via the service's param channel; the replay state never
    crosses into actor land at all."""

    env_state: Pytree
    obs: jax.Array
    episode_return: jax.Array
    last_return: jax.Array


def init_actor_slice(v_reset: Callable, key: jax.Array, n_envs: int,
                     shard_id: int = 0) -> ActorSlice:
    env_state, obs = v_reset(jax.random.fold_in(key, shard_id))
    return ActorSlice(env_state=env_state, obs=obs,
                      episode_return=jnp.zeros((n_envs,)),
                      last_return=jnp.zeros((n_envs,)))


def make_actor_program(agent: Agent, v_step: Callable, cfg: LoopConfig,
                       n_envs: int):
    """The actor side of the split runtime: one jit-able program that
    turns (acting params, env slice, rng, global env-step clock) into a
    transition batch — no replay state, no learner coupling.  The
    ε-schedule is computed *inside* the program from the integer
    ``env_steps`` clock (the service reports global inserts), so a
    host-driven actor reproduces the fused loop's exploration bit-exactly.

    Returns ``program(agent_state, slice, k_act, k_env, env_steps) →
    (slice', transitions)``; the caller jits it (once) and owns the rng
    chain and the append to the replay service.
    """
    actor_step = make_actor_step(agent, v_step, n_envs)

    def program(agent_state, sl: ActorSlice, k_act, k_env, env_steps):
        eps = epsilon_schedule(cfg, env_steps)
        env_state, obs, ep_ret, last_ret, transitions = actor_step(
            agent_state, sl.env_state, sl.obs,
            sl.episode_return, sl.last_return, k_act, k_env, eps)
        return ActorSlice(env_state, obs, ep_ret, last_ret), transitions

    return program


# -- learner program ---------------------------------------------------------


def make_learner_program(agent: Agent):
    """The learner side of the split runtime (DESIGN.md §11): consume a
    sampled batch handed over the service boundary, return the TD errors
    the service needs for the priority write-back.  No replay state —
    sample and priority update live behind the service; this program is
    everything the learner process owns.  ``make_learner_step`` below is
    its fused composition with an in-program replay shard.

    Returns ``program(agent_state, items, weights) →
    (agent_state, metrics, td_errors)``; the caller jits it.
    """

    def program(agent_state, items, weights):
        return agent.learn(agent_state, items, weights)

    return program


def make_learner_step(agent: Agent, replay, cfg: LoopConfig):
    """One parallel-learner call: PER sample → TD update → priority
    write-back (write-after-read tolerated, §IV-D3; with
    ``cfg.lazy_replay`` the write-back is leaf-only and rides the next
    flush).

    ``replay`` may be a ``PrioritizedReplay`` or any object with the same
    sample/update_priorities signature (e.g. the sharded buffer, whose
    ``sample`` computes importance weights against psum'd global stats).
    The sharded gradient-psum variant lives in ``runtime/learner.py``;
    ``age`` (the staleness of the caller's acting copy) and ``ef`` (the
    error-feedback buffer of the compressed cross-pod reduce) are part of
    the shared learn-fn signature and are passed through unused here —
    only the sharded reduces consume them.  Learn fns return a metrics
    dict with ``LEARN_METRIC_KEYS`` (the fused path has no compressed
    reduce, so its error norm is 0).
    """

    def learner_step(agent_state, replay_state, rng, age=None, ef=None):
        del age  # fused learner: no cross-shard reduce to weight
        # phase scopes (runtime/phases.py): HLO metadata only
        with jax.named_scope("sample"):
            idx, items, is_w = replay.sample(replay_state, rng,
                                             cfg.batch_size, cfg.beta)
        with jax.named_scope("learner_update"):
            agent_state, metrics, td = agent.learn(agent_state, items, is_w)
        with jax.named_scope("write_back"):
            replay_state = replay.update_priorities(replay_state, idx, td,
                                                    lazy=cfg.lazy_replay)
        lmetrics = {"loss": metrics["loss"],
                    "compress_error_norm": jnp.zeros(())}
        return agent_state, replay_state, lmetrics, ef

    return learner_step


# -- composed step -----------------------------------------------------------


def make_step(
    agent: Agent,
    replay,
    v_step: Callable,
    cfg: LoopConfig,
    n_envs: int,
    *,
    schedule: Optional[RatioSchedule] = None,
    learn_fn: Optional[Callable] = None,
    shard_id: Union[int, Callable[[], jax.Array]] = 0,
    mean_across: Optional[Callable] = None,
    sum_across: Optional[Callable] = None,
    publish_interval: int = 0,
    external_publish: bool = False,
):
    """Compose actor + learner programs into one jit-able parallel_step.

    ``n_envs`` is the *local* env count (per shard); ``schedule`` carries
    the global env steps per iteration.  ``shard_id`` feeds the per-shard
    rng fold (a callable so ``lax.axis_index`` can be read inside
    ``shard_map``); ``mean_across``/``sum_across`` reduce reported metrics
    over shards (identity when fused).

    ``publish_interval=0`` is the synchronous loop: actors act on the
    fresh ``state.agent``.  ``publish_interval=P ≥ 1`` is the async loop:
    actors act on ``state.actor_params`` (snapshotted by
    ``init_loop_state(double_buffer=True)``), and at the end of iteration
    ``it`` shard ``d`` republishes its acting copy from the fresh learner
    params iff ``(it + 1 + d) % P == 0`` — the per-shard stagger
    decorrelates the shard clocks, so under ``shard_map`` the shards
    carry *different* parameter ages (0..P-1) and the bounded-staleness
    reduce has real work to do.  ``state.params_age`` is handed to
    ``learn_fn`` so that reduce can weight this shard's gradient.  At
    ``P=1`` every shard republishes every iteration and the async loop is
    the synchronous one (asserted trajectory-exact in
    tests/test_async_executor.py).

    ``external_publish=True`` (wall-clock mode, DESIGN.md §10) keeps the
    async acting-copy *reads* but removes the in-program republish: the
    host runtime owns the publish, performing a real device→host
    parameter transfer between chunks and rewriting
    ``actor_params``/``params_age`` on the carried state
    (``launch/multiprocess.py``).  ``params_age`` then just increments
    every iteration so the staleness-weighted reduce still sees honest
    ages between host publishes.
    """
    if external_publish and not publish_interval:
        raise ValueError(
            "external_publish=True needs publish_interval ≥ 1: the host "
            "publish rewrites the async acting copy, which only exists "
            "on the double-buffered (publish_interval > 0) loop")
    schedule = schedule or RatioSchedule.from_config(cfg, n_envs)
    actor_step = make_actor_step(agent, v_step, n_envs)
    learn_fn = learn_fn or make_learner_step(agent, replay, cfg)
    mean_across = mean_across or (lambda x: x)
    sum_across = sum_across or (lambda x: x)

    def step(state: LoopState) -> Tuple[LoopState, Dict[str, jax.Array]]:
        # each numbered step runs under its phase's named scope
        # (runtime/phases.py): HLO metadata only, the ops are unchanged

        # 1. parallel actors — on the delayed double-buffered copy when
        #    async, on the fresh learner params when synchronous
        with jax.named_scope("act"):
            rng_next, k = jax.random.split(state.rng)
            sid = shard_id() if callable(shard_id) else shard_id
            k = jax.random.fold_in(k, sid)
            k_act, k_env, k_sample = jax.random.split(k, 3)
            acting = (agent.with_acting_params(state.agent,
                                               state.actor_params)
                      if publish_interval else state.agent)
            eps = epsilon_schedule(cfg, state.env_steps)
            env_state, obs_next, ep_ret, last_ret, transitions = actor_step(
                acting, state.env_state, state.obs,
                state.episode_return, state.last_return, k_act, k_env, eps)

        # 2. lazy write, phase 1: zero the in-flight slots' leaf
        #    priorities (propagation deferred to the flush below)
        lazy = cfg.lazy_replay
        with jax.named_scope("insert_begin"):
            replay_state, slots = replay.insert_begin(state.replay, n_envs,
                                                      lazy=lazy)

        # 3. THE flush boundary: one merged upward-propagation pass per
        #    iteration, coalescing the previous iteration's priority
        #    updates + insert-commit with this iteration's insert-begin.
        #    After this the tree is consistent and the in-flight slots
        #    are unsampleable (lazy ≡ eager bit-exact at this point).
        if lazy:
            with jax.named_scope("flush"):
                replay_state = replay.flush(replay_state)

        # 4. parallel learners on the flushed tree state, at the scheduled
        #    collection/consumption ratio — always on the fresh params
        def do_learn(args):
            agent_state, rstate, ef = args
            acc = {k: jnp.zeros(()) for k in LEARN_METRIC_KEYS}
            for i in range(schedule.learns):
                if lazy and i:
                    # extra learner calls in the same event must also
                    # sample a consistent tree: flush the previous
                    # call's priority write-back first
                    with jax.named_scope("flush"):
                        rstate = replay.flush(rstate)
                ki = jax.random.fold_in(k_sample, i)
                agent_state, rstate, lmetrics, ef = learn_fn(
                    agent_state, rstate, ki, age=age, ef=ef)
                acc = {k: acc[k] + lmetrics[k] for k in acc}
            means = {k: v / schedule.learns for k, v in acc.items()}
            return (agent_state, rstate, means,
                    state.learn_steps + schedule.learns, ef)

        def skip_learn(args):
            agent_state, rstate, ef = args
            zeros = {k: jnp.zeros(()) for k in LEARN_METRIC_KEYS}
            return agent_state, rstate, zeros, state.learn_steps, ef

        with jax.named_scope("learn"):
            it = state.env_steps // schedule.env_steps_per_iter
            can_learn = ((state.env_steps >= cfg.warmup)
                         & (it % schedule.period == 0))
            age = (state.params_age if publish_interval
                   else jnp.zeros((), jnp.int32))
            (agent_state, replay_state, lmetrics, learn_steps,
             ef_error) = jax.lax.cond(
                can_learn, do_learn, skip_learn,
                (state.agent, replay_state, state.ef_error))

        # 6. lazy write, phase 3: storage write + P_max restore (the
        #    leaf write is eager, its propagation rides the next flush)
        with jax.named_scope("insert_commit"):
            replay_state = replay.insert_commit(replay_state, slots,
                                                transitions, lazy=lazy)

        # 7. async publish: refresh this shard's acting copy from the
        #    fresh learner params on its (staggered) publish tick —
        #    unless the host runtime owns the publish (wall-clock mode:
        #    real D2H transfer between chunks, age just keeps counting)
        if publish_interval:
            with jax.named_scope("publish"):
                if external_publish:
                    actor_params = state.actor_params
                    params_age = state.params_age + 1
                else:
                    publish = (it + 1 + sid) % publish_interval == 0
                    actor_params = jax.tree.map(
                        lambda fresh, held: jnp.where(publish, fresh, held),
                        agent.params_for_acting(agent_state),
                        state.actor_params)
                    params_age = jnp.where(publish, 0, state.params_age + 1)
        else:
            actor_params, params_age = state.actor_params, state.params_age

        new_state = LoopState(
            agent=agent_state,
            replay=replay_state,
            env_state=env_state,
            obs=obs_next,
            rng=rng_next,
            env_steps=state.env_steps + schedule.env_steps_per_iter,
            episode_return=ep_ret,
            last_return=last_ret,
            learn_steps=learn_steps,
            actor_params=actor_params,
            params_age=params_age,
            ef_error=ef_error,
        )
        metrics = {
            "loss": mean_across(lmetrics["loss"]),
            "mean_episode_return": mean_across(jnp.mean(last_ret)),
            "env_steps": new_state.env_steps,
            "learn_steps": learn_steps,
            "buffer_size": sum_across(replay_state.count),
            "epsilon": eps,
            "compress_error_norm": mean_across(
                lmetrics["compress_error_norm"]),
        }
        assert set(metrics) == set(METRIC_KEYS)
        return new_state, metrics

    return step


def make_parallel_step(
    agent: Agent,
    replay: PrioritizedReplay,
    v_step: Callable,
    cfg: LoopConfig,
    n_envs: int,
):
    """Returns jit-able parallel_step(state) → (state, metrics) — the
    fused single-device composition (compat wrapper over ``make_step``)."""
    return make_step(agent, replay, v_step, cfg, n_envs)


def init_loop_state(
    agent: Agent,
    replay,
    v_reset: Callable,
    key: jax.Array,
    n_envs: int,
    shard_id: Union[int, jax.Array] = 0,
    double_buffer: bool = False,
    ef_buffer: bool = False,
    overlap: bool = False,
) -> LoopState:
    """Initial state.  ``shard_id`` decorrelates per-shard env resets while
    agent params (from the unfolded key) stay replicated across shards.
    ``double_buffer`` fills the async acting copy (``actor_params`` at age
    0, i.e. identical to the fresh params); ``ef_buffer`` fills the
    zero-initialized error-feedback buffer of the compressed cross-pod
    reduce (the gradient pytree of agents with the grads/apply_grads
    split matches ``state.params``, so params is the template);
    ``overlap`` widens it to the double-buffered reduce's ``{"ef",
    "prev_mean", "prev_partial"}`` triple — the quantizer residual plus
    the zero-initialized previous-event pod mean and intra-pod partial
    (``make_grad_reducer(..., overlap=True)``).  The synchronous/
    uncompressed executors leave these fields as empty pytrees — no
    memory overhead."""
    k1, k2, k3 = jax.random.split(key, 3)
    env_state, obs = v_reset(jax.random.fold_in(k1, shard_id))
    agent_state = agent.init(k2)
    return LoopState(
        agent=agent_state,
        replay=replay.init(),
        env_state=env_state,
        obs=obs,
        rng=k3,
        env_steps=jnp.zeros((), jnp.int32),
        episode_return=jnp.zeros((n_envs,)),
        last_return=jnp.zeros((n_envs,)),
        learn_steps=jnp.zeros((), jnp.int32),
        actor_params=(agent.params_for_acting(agent_state)
                      if double_buffer else ()),
        params_age=jnp.zeros((), jnp.int32) if double_buffer else (),
        ef_error=(({"ef": compress.init_error(agent_state.params),
                    "prev_mean": compress.init_error(agent_state.params),
                    "prev_partial": compress.init_error(agent_state.params)}
                   if overlap else compress.init_error(agent_state.params))
                  if ef_buffer else ()),
    )


def train(
    agent: Agent,
    replay: PrioritizedReplay,
    v_reset: Callable,
    v_step: Callable,
    cfg: LoopConfig,
    n_envs: int,
    iterations: int,
    key: jax.Array,
    log_every: int = 0,
    scan_chunk: int = 64,
) -> Tuple[LoopState, Dict[str, jax.Array]]:
    """Run the full fused loop — a thin wrapper over ``FusedExecutor``
    for callers that already hold (v_reset, v_step) instead of an env
    factory."""
    from repro.runtime.executors import FusedExecutor  # lazy: avoid cycle

    ex = FusedExecutor(agent, replay, lambda _n: (None, v_reset, v_step),
                       cfg, n_envs, scan_chunk=scan_chunk)
    return ex.train(iterations, key, log_every)
