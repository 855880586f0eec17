"""The general generator and runner of a training cell.

A cell is one configuration (its algorithm family's module in
``perfbench/algorithms/`` makes the agent, env and row; replay sizes)
under one traffic mix (actors, batch, collection/learning ratio, tree
backend, mesh).  ``run`` builds the executor the user would build,
fills the replay to capacity through the replay's own insert path with
the family's env under a uniformly random policy, compiles, and drives
the first chunk through the executor's own ``run_chunk``: that chunk is
the warm-up and the probe that the comparison with the plain reference
reads.  The same executor and state then run the measured window
(``--trace 0``) or a short profiled window (``--trace 1``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from perfbench import algorithms, check, reference, trace_reduce, work
from perfbench.peaks import peaks_for
from perfbench.spec import Cell


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


class CompileClock:
    """Backend compilations (count and seconds; a persistent-cache hit
    counts its retrieval) since the last ``take``."""

    def __init__(self):
        import jax

        self._secs, self._n = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += secs
            self._n += 1

    def take(self):
        out = (self._n, self._secs)
        self._secs, self._n = 0.0, 0
        return out


@dataclasses.dataclass
class Built:
    executor: object
    replay: object
    n_shards: int
    capacity: int           # rows per shard
    n_envs: int             # global actors
    batch: int              # global learner batch
    learns: int             # learner updates per iteration
    mesh: object = None
    init: object = None     # jitted executor init, made at first probe
    fill: object = None     # make_fill's program, made at first probe


def build(cell: Cell) -> Built:
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.optim.adam import AdamConfig
    from repro.runtime.executors import FusedExecutor, ShardedExecutor
    from repro.runtime.loop import LoopConfig

    c, t = cell.config, cell.traffic
    opt = AdamConfig(lr=c["learning_rate"], b1=c["adam_b1"], b2=c["adam_b2"],
                     eps=c["adam_eps"], grad_clip=c["grad_clip_norm"])
    agent, env_fn, example = algorithms.of(c).build(c, t, opt)
    loop = LoopConfig(batch_size=t["batch_size"],
                      update_interval=t["update_interval"], warmup=0,
                      epsilon=t["epsilon"], epsilon_final=t["epsilon_final"],
                      epsilon_decay_steps=t["epsilon_decay_steps"],
                      beta=c["per_beta"], lazy_replay=c["lazy_replay"])
    cap = c["replay_capacity"]
    mesh_spec = t.get("mesh")
    if mesh_spec is None:
        replay = PrioritizedReplay(
            ReplayConfig(capacity=cap, fanout=c["fanout"],
                         alpha=c["per_alpha"], eps=c["per_eps"],
                         backend=t["replay_backend"]), example)
        ex = FusedExecutor(agent, replay, env_fn, loop, t["n_envs"],
                           scan_chunk=t["scan_chunk"])
        mesh, n_shards = None, 1
    else:
        from repro.core.distributed import (ShardedPrioritizedReplay,
                                            ShardedReplayConfig)
        from repro.launch.mesh import data_mesh, pod_data_mesh

        axes, shape = tuple(mesh_spec["axes"]), tuple(mesh_spec["shape"])
        mesh = data_mesh(shape[0], axes[0]) if len(axes) == 1 else \
            pod_data_mesh(*shape, axes=axes)
        replay = ShardedPrioritizedReplay(
            ShardedReplayConfig(capacity_per_shard=cap, fanout=c["fanout"],
                                alpha=c["per_alpha"], eps=c["per_eps"],
                                backend=t["replay_backend"], axis_names=axes),
            example)
        ex = ShardedExecutor(agent, replay, env_fn, loop, t["n_envs"], mesh,
                             scan_chunk=t["scan_chunk"])
        n_shards = math.prod(shape)
    return Built(executor=ex, replay=replay, n_shards=n_shards, capacity=cap,
                 n_envs=t["n_envs"], batch=t["batch_size"],
                 learns=ex.schedule.learns, mesh=mesh)


def make_fill(b: Built, cell: Cell):
    """Jitted ``fill(key, replay_state) → (replay_state, rows)``: every
    shard's buffer gets ``capacity`` rows of the family's fill (a
    uniformly random policy), appended through the replay's own writer
    transaction and flushed once.  ``rows`` is that input, as the
    reference reads it."""
    import jax
    import jax.numpy as jnp

    n = cell.traffic["fill_envs"]
    cap = b.capacity
    if cap % n:
        raise ValueError(f"capacity {cap} is not a multiple of fill_envs {n}")
    start, step = algorithms.of(cell.config).fill(cell.config, n)

    def fill_local(key, rs):
        k0, k1 = jax.random.split(key)

        def body(carry, k):
            carry, rs = carry
            carry, row = step(carry, k)
            rs = b.replay.append(rs, row, lazy=True)
            return (carry, rs), row

        (_, rs), rows = jax.lax.scan(body, (start(k0), rs),
                                     jax.random.split(k1, cap // n))
        rows = jax.tree.map(lambda x: x.reshape((cap,) + x.shape[2:]), rows)
        return b.replay.flush(rs), rows

    if b.mesh is None:
        return jax.jit(fill_local, donate_argnums=1)
    from jax.sharding import PartitionSpec as P

    axes = tuple(b.mesh.axis_names)
    sizes = [b.mesh.shape[a] for a in axes]

    def local(key, rs_g):
        sid = jnp.zeros((), jnp.int32)
        for ax, size in zip(axes, sizes):
            sid = sid * size + jax.lax.axis_index(ax)
        rs, rows = fill_local(jax.random.fold_in(key, sid),
                              jax.tree.map(lambda x: x[0], rs_g))
        lead = lambda t: jax.tree.map(lambda x: x[None], t)
        return lead(rs), lead(rows)

    dim0 = P(axes)
    return jax.jit(jax.shard_map(local, mesh=b.mesh, in_specs=(P(), dim0),
                                 out_specs=(dim0, dim0), check_vma=False),
                   donate_argnums=1)


def _drive(ex, state, seconds: float, in_flight: int):
    """Run chunks, ``in_flight`` of them queued on the device at a time,
    until ``seconds`` have passed, then wait for all that was sent.  The
    chunks queued behind the one waited for keep the chip fed while the
    host stands still; losses are read as each chunk completes, and the
    longest time between two completions is logged (a stall of the host
    longer than the queue shows there).  Returns (state, chunks, seconds
    from the first dispatch to the end of the last chunk, losses)."""
    import collections

    import jax

    t0, last = time.perf_counter(), None
    pending, chunks, losses, longest = collections.deque(), 0, [], 0.0
    while True:
        while len(pending) < in_flight and time.perf_counter() - t0 < seconds:
            state, m = ex.run_chunk(state)
            pending.append(m)
        if not pending:
            log(f"window: longest time between chunk completions "
                f"{longest:.4f} s")
            return state, chunks, time.perf_counter() - t0, losses
        m = pending.popleft()
        jax.block_until_ready(m["loss"])
        now = time.perf_counter()
        if last is not None:
            longest = max(longest, now - last)
        last = now
        chunks += 1
        losses.append(m["loss"])


def _run_chunks(ex, state, n: int):
    """Dispatch ``n`` chunks back to back and wait for the last: the
    traced window, sized so that the profiler keeps every event."""
    import jax

    t0 = time.perf_counter()
    ms = []
    for _ in range(n):
        state, m = ex.run_chunk(state)
        ms.append(m)
    losses = [m["loss"] for m in ms]
    jax.block_until_ready(losses)
    return state, n, time.perf_counter() - t0, losses


def _check_coverage(reduced, window_s: float):
    """The profiler keeps a bounded number of device events and drops
    the rest: a trace whose ops stop short of the window would read the
    dropped time as idle, so it is refused."""
    for d in reduced.devices:
        held = (d.last_ns - d.first_ns) / 1e9
        if held < 0.9 * window_s:
            raise SystemExit(
                f"perfbench: the trace of {d.name} holds {held:.4f} s of the "
                f"{window_s:.4f} s window: the profiler dropped events; "
                "trace fewer chunks (traffic key trace_chunks)")


def _device_info(cell: Cell, devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": cell.chips}


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Probed:
    built: Built
    state: object           # the loop state after the probe chunk
    rows: object            # the fill's rows, on the device
    probe: dict             # what the comparison reads of the program
    devices: list


def probe(cell: Cell, seed: int, b: Optional[Built] = None) -> Probed:
    """Build (unless ``b`` is given), fill, compile and run the first
    chunk (the probe)."""
    import jax

    b = b or build(cell)
    ex = b.executor
    devices = (list(b.mesh.devices.flat) if b.mesh is not None
               else [jax.devices()[0]])
    key = jax.numpy.asarray(reference.seed_key(seed))
    if b.init is None:
        b.init = ex.init if b.mesh is not None else jax.jit(ex.init)
        b.fill = make_fill(b, cell)
    state = b.init(key)
    replay_state, rows = b.fill(
        jax.random.fold_in(key, 0x5EED), state.replay)
    state = state._replace(replay=replay_state)
    del replay_state

    state, m = ex.run_chunk(state)
    jax.block_until_ready(m["loss"])
    spec = ex.replay.spec if b.mesh is None else ex.replay.local.spec
    off = spec.leaf_offset
    tree = state.replay.tree
    leaves = (tree[None, off:off + b.capacity] if b.mesh is None
              else tree[:, off:off + b.capacity])
    return Probed(built=b, state=state, rows=rows, devices=devices, probe={
        "loss0": float(m["loss"][0]),
        "env_steps": int(m["env_steps"][-1]),
        "learn_steps": int(m["learn_steps"][-1]),
        "buffer_size": int(m["buffer_size"][-1]),
        "optimizer_steps": _optimizer_steps(state.agent.opt),
        "leaves": np.asarray(jax.device_get(leaves)),
        "param_spread": _param_spread(state.agent.params),
    })


def expected_counters(cell: Cell, b: Built) -> dict:
    iters = cell.traffic["scan_chunk"]
    return {"env_steps": iters * b.executor.schedule.env_steps_per_iter,
            "learn_steps": iters * b.learns,
            "optimizer_steps": iters * b.learns,
            "buffer_size": b.capacity * b.n_shards}


def host_rows(b: Built, rows) -> list:
    """The fill's rows on the host, one dict per shard."""
    import jax

    got = {k: np.asarray(v) for k, v in jax.device_get(rows).items()}
    if b.mesh is None:
        return [got]
    return [{k: v[d] for k, v in got.items()} for d in range(b.n_shards)]


def follow(cell: Cell, b: Built, seed: int, rows: list, dtype=None,
           device=None):
    """The reference (float32, highest) or, with ``dtype=bfloat16``, the
    control over the first iteration."""
    import jax.numpy as jnp

    return reference.follow_first_iteration(
        cell.config, seed, rows, b.n_envs // b.n_shards,
        b.batch // b.n_shards, b.learns, dtype=dtype or jnp.float32,
        device=device)


def run(cell: Cell, seed: int, seconds: float, trace: bool, clock,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    p = probe(cell, seed)
    b, ex, state = p.built, p.built.executor, p.state
    p.state = None
    n_compiles, compile_s = clock.take()
    log(f"set-up: {n_compiles} compiles, {compile_s:.3f} s compiling")
    setup_s = time.perf_counter() - t_start

    out: Dict[str, dict] = {}
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": out,
              "device": _device_info(cell, p.devices)}
    chunk_iters = cell.traffic["scan_chunk"]
    if not trace:
        state, chunks, window_s, losses = _drive(
            ex, state, seconds, cell.traffic["chunks_in_flight"])
        iters = chunks * chunk_iters
        out["env_steps_per_s"] = {
            "value": iters * ex.schedule.env_steps_per_iter / window_s,
            "unit": "env_steps/s"}
        out["setup_s"] = {"value": setup_s, "unit": "s"}
        log(f"window: {chunks} chunks of {chunk_iters} iterations in "
            f"{window_s:.4f} s")
    else:
        tdir = tempfile.mkdtemp(prefix="perfbench_trace_")
        jax.profiler.start_trace(tdir)
        try:
            state, chunks, window_s, losses = _run_chunks(
                ex, state, cell.traffic["trace_chunks"])
        finally:
            jax.profiler.stop_trace()
        iters = chunks * chunk_iters
        reduced = trace_reduce.reduce_file(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        _check_coverage(reduced, window_s)
        ctx = {"reduced": reduced, "window_s": window_s, "iterations": iters,
               "chips": cell.chips, "config": cell.config,
               "traffic": cell.traffic, "learns": b.learns,
               "peaks": peaks_for(p.devices[0].device_kind), "work": work}
        for metric in cell.per_layer:
            value = cell.readers[metric["name"]](ctx)
            if value is None:
                log(f"metric {metric['name']}: nothing to read in the trace")
            else:
                out[metric["name"]] = {"value": value, "unit": metric["unit"]}
        result["device"]["busy_s"] = reduced.busy_s_mean
        result["device"]["window_s"] = window_s
        result["breakdown"] = trace_reduce.breakdown(reduced)
        log(f"traced window: {chunks} chunks in {window_s:.4f} s, "
            f"{len(reduced.devices)} device planes")
    n_compiles, compile_s = clock.take()
    log(f"compiles inside the window: {n_compiles} ({compile_s:.3f} s)")
    losses = np.concatenate([np.asarray(x) for x in losses])
    result["attempted"] = int(iters)
    result["failed"] = int(np.sum(~np.isfinite(losses)))
    result["device"]["memory_peak_bytes"] = _memory_peak(p.devices)

    # free the program's state, then follow the first iteration
    rows = host_rows(b, p.rows)
    p.rows = None
    del state
    gc.collect()
    followed = follow(cell, b, seed, rows)
    checks = check.compare(p.probe, followed, expected_counters(cell, b),
                           cell.limits)
    result["correct"] = check.all_within(checks) and result["failed"] == 0
    result["checks"] = checks
    return result


def _optimizer_steps(opt) -> int:
    """The optimizer's own step count (its first copy on a mesh)."""
    return int(np.asarray(opt.count.addressable_shards[0].data).reshape(-1)[0])


def _param_spread(params) -> float:
    """Largest difference between the copies of a replicated parameter
    on its devices (0 on one chip)."""
    import jax

    spread = 0.0
    for leaf in jax.tree.leaves(params):
        copies = [np.asarray(sh.data) for sh in leaf.addressable_shards]
        for c in copies[1:]:
            spread = max(spread, float(np.max(np.abs(c - copies[0]))))
    return spread
