"""Fig. 10 — scalability of DQN/DDPG/SAC vs parallel-actor count.

The paper scales CPU cores; the JAX adaptation scales vectorized actor
lanes (the same resource axis the DSE allocates).  Reports env-steps/s
per algorithm at 1/2/4/8/16 lanes and derived speedup vs 1 lane, through
the FusedExecutor.

A second mode sweeps *runtime shards*: ``--shards 1,2,4`` re-launches
this script in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax initializes) and times the ShardedExecutor — DQN through
the sharded replay + psum'd learner — at each shard count.

A third mode measures the **wall-clock** arm (``--wall-clock``,
DESIGN.md §10): each point is a real multi-process gang launched
through ``launch/multiprocess.py`` — separate OS processes, one XLA
client each, gloo collectives over real process boundaries — timing
the same DQN/CartPole workload as the emulated arms (median-of-N with
``rel_spread`` inside the worker).  These land in BENCH_fig10.json as
``backend="wallclock"`` points carrying ``n_procs``/``overlapped``/
``update_interval`` identity fields, so the runtime planner can prefer
them over the emulated measurements of the same config.
"""

import argparse
import functools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.agents.ddpg import DDPGConfig, make_ddpg
from repro.agents.dqn import DQNConfig, make_dqn
from repro.agents.sac import SACConfig, make_sac
from repro.core.replay import PrioritizedReplay, ReplayConfig
from repro.envs.classic import make_vec
from repro.runtime import loop
from repro.runtime.executors import FusedExecutor


def example(spec):
    return {
        "obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "action": (jnp.zeros((), jnp.int32) if spec.discrete
                   else jnp.zeros((spec.action_dim,), jnp.float32)),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "done": jnp.zeros(()),
    }


ALGOS = {
    "dqn": ("cartpole", lambda s: make_dqn(s, DQNConfig())),
    "ddpg": ("pendulum", lambda s: make_ddpg(s, DDPGConfig())),
    "sac": ("pendulum", lambda s: make_sac(s, SACConfig())),
}


def _time_executor_stats(ex, iters: int, repeats=None):
    """(median env-steps/s, rel_spread) of a warmed executor over
    ``repeats`` passes of ``iters`` iterations (benchmarks/timing.py)."""
    from benchmarks.timing import REPEATS, median_with_spread

    st = ex.init(jax.random.PRNGKey(0))
    st, _ = ex.run_chunk(st)
    jax.block_until_ready(st.obs)
    n_chunks = max(1, iters // ex.scan_chunk)
    state = [st]

    def probe():
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            state[0], _ = ex.run_chunk(state[0])
        jax.block_until_ready(state[0].obs)
        dt = time.perf_counter() - t0
        return ex.n_envs * ex.scan_chunk * n_chunks / dt

    return median_with_spread(probe, REPEATS if repeats is None else repeats)


def _time_executor(ex, iters: int) -> float:
    """Single-shot env-steps/s (no repeats) — kept for quick sweeps."""
    return _time_executor_stats(ex, iters, repeats=1)[0]


def throughput(algo: str, n_envs: int, iters: int = 120) -> float:
    env_name, mk = ALGOS[algo]
    env_fn = functools.partial(make_vec, env_name)
    spec, _, _ = env_fn(1)
    agent = mk(spec)
    replay = PrioritizedReplay(ReplayConfig(capacity=50_000, fanout=128),
                               example(spec))
    cfg = loop.LoopConfig(batch_size=64, warmup=64, epsilon=0.1)
    ex = FusedExecutor(agent, replay, env_fn, cfg, n_envs, scan_chunk=20)
    return _time_executor(ex, iters)


def _sharded_executor_throughput(mesh_fn, axis_names, n_cells: int,
                                 compress: bool, n_envs: int,
                                 iters: int):
    """Shared setup for the sharded-throughput workers: DQN/CartPole
    through a ShardedExecutor over ``mesh_fn()`` with one replay shard
    per mesh cell (run inside a process whose forced device count ≥ the
    cell count).  Returns (median env-steps/s, rel_spread)."""
    from repro.core.distributed import (ShardedPrioritizedReplay,
                                        ShardedReplayConfig)
    from repro.runtime.executors import ShardedExecutor

    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    agent = ALGOS["dqn"][1](spec)
    replay = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=50_000 // n_cells, fanout=128,
                            axis_names=axis_names), example(spec))
    cfg = loop.LoopConfig(batch_size=64, warmup=64, epsilon=0.1)
    ex = ShardedExecutor(agent, replay, env_fn, cfg, n_envs, mesh_fn(),
                         scan_chunk=20, compress_pod_reduce=compress)
    return _time_executor_stats(ex, iters)


def sharded_throughput(n_shards: int, n_envs: int = 16, iters: int = 120):
    """1-D data-axis ShardedExecutor (median env-steps/s, rel_spread)
    at ``n_shards``."""
    from repro.launch.mesh import data_mesh

    return _sharded_executor_throughput(
        lambda: data_mesh(n_shards), ("data",), n_shards, False, n_envs,
        iters)


def run(csv=True):
    rows = []
    for algo in ALGOS:
        base = None
        for n in (1, 2, 4, 8, 16):
            t = throughput(algo, n)
            base = base or t
            rows.append((f"fig10/{algo}_{n}actors", 1e6 / t, t / base))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived:.2f}")
    return rows


def pod_sharded_throughput(n_pods: int, n_data: int, compress: bool,
                           n_envs: int = 16, iters: int = 120):
    """Two-axis pod×data ShardedExecutor (median env-steps/s,
    rel_spread), optionally with the int8-EF compressed cross-pod
    reduce."""
    from repro.launch.mesh import pod_data_mesh

    return _sharded_executor_throughput(
        lambda: pod_data_mesh(n_pods, n_data), ("pod", "data"),
        n_pods * n_data, compress, n_envs, iters)


def _run_worker(worker_args, n_devices, n_envs=16, iters=120):
    """Launch this script as a subprocess with the forced device count
    (the XLA flag must be set before jax initializes) and parse the
    STEPS_PER_S= line.  Forced host devices exist only on the CPU, and
    the parent may hold the accelerator, so the child is pinned to the
    CPU: its points are CPU points (``platform="cpu"``)."""
    script = os.path.abspath(__file__)
    root = os.path.dirname(os.path.dirname(script))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"{env.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={n_devices}").strip()
    # src for the repro package, root for benchmarks.* (the worker runs
    # as a script, so its sys.path[0] is benchmarks/, not the repo root)
    src = os.path.join(root, "src")
    paths = f"{src}:{root}"
    env["PYTHONPATH"] = (f"{paths}:{env['PYTHONPATH']}"
                         if env.get("PYTHONPATH") else paths)
    worker_args = worker_args + ["--n-envs", str(n_envs),
                                 "--iters", str(iters)]
    r = subprocess.run([sys.executable, script] + worker_args,
                       capture_output=True, text=True, timeout=1200,
                       env=env, cwd=root)
    out = [line for line in r.stdout.splitlines()
           if line.startswith("STEPS_PER_S=")]
    if not out:
        raise RuntimeError(
            f"worker {worker_args} failed:\n{r.stdout}\n{r.stderr}")
    spreads = [line for line in r.stdout.splitlines()
               if line.startswith("REL_SPREAD=")]
    spread = float(spreads[-1].split("=")[1]) if spreads else 0.0
    return float(out[-1].split("=")[1]), spread


def run_shard_sweep(shard_counts, csv=True):
    """Sweep --xla_force_host_platform_device_count via subprocesses."""
    rows = []
    base = None
    for n in shard_counts:
        t, _ = _run_worker(["--_sharded-worker", str(n)], n)
        base = base or t
        rows.append((f"fig10/sharded_{n}shards", 1e6 / t, t / base))
    if csv:
        for name, us, derived in rows:
            print(f"{name},{us:.2f},{derived:.2f}")
    return rows


def shard_pod_points(shard_counts=(1, 2), pod_specs=((2, 1, False),
                                                     (2, 2, False),
                                                     (2, 2, True)),
                     n_envs=16, iters=120):
    """Machine-readable env-steps/s per shard/pod count for
    BENCH_fig10.json: 1-D data-axis counts plus (n_pods, n_data,
    compressed) two-axis points, each in its own forced-device
    subprocess."""
    from benchmarks.timing import REPEATS

    points = []
    for n in shard_counts:
        t, spread = _run_worker(["--_sharded-worker", str(n)], n,
                                n_envs=n_envs, iters=iters)
        points.append({"backend": "sharded", "shards": n, "pods": 1,
                       "compressed": False, "n_envs": n_envs,
                       "platform": "cpu",
                       "env_steps_per_s": round(t, 2),
                       "repeats": REPEATS, "rel_spread": round(spread, 4)})
    for n_pods, n_data, compress in pod_specs:
        t, spread = _run_worker(
            ["--_pod-worker", f"{n_pods},{n_data},{int(compress)}"],
            n_pods * n_data, n_envs=n_envs, iters=iters)
        points.append({"backend": "sharded_pod_data", "shards": n_data,
                       "pods": n_pods, "compressed": bool(compress),
                       "n_envs": n_envs, "platform": "cpu",
                       "env_steps_per_s": round(t, 2),
                       "repeats": REPEATS, "rel_spread": round(spread, 4)})
    return points


# the wall-clock sweep: (n_procs, n_pods, n_data, compress, overlap).
# shards=1 and 2 cover the data axis; the pods=2 pair measures the
# barrier vs the double-buffered overlapped compressed reduce on a real
# 2-process gang.  update_interval=8 (one learn event per iteration at
# 8 envs) is the regime where the overlap pays: the cross-pod
# collective issued at learn i is consumed at learn i+1, so it runs
# concurrently with the next actor chunk; at update_interval=1 the next
# learn in the SAME iteration consumes the carry immediately and there
# is no window (measured in DESIGN.md §10).
WALLCLOCK_SPECS = (
    (1, 1, 1, False, False),
    (1, 1, 2, False, False),
    (2, 1, 2, False, False),
    (2, 2, 1, True, False),
    (2, 2, 1, True, True),
)


def wallclock_points(specs=WALLCLOCK_SPECS, n_envs=8, iters=40,
                     update_interval=8, repeats=3, scan_chunk=20):
    """Real multi-process gang throughput for BENCH_fig10.json: one
    ``launch.multiprocess`` gang per spec, the bench worker reporting
    median-of-``repeats`` env-steps/s with its rel_spread.  All points
    share ``n_envs`` (the global env count splits across mesh cells) so
    they are mutually comparable — and comparable with the emulated
    arms at the same env count, up to the recorded update_interval."""
    from repro.launch import multiprocess as mp

    points = []
    for n_procs, n_pods, n_data, compress, overlap in specs:
        n_cells = n_pods * n_data
        if n_cells % n_procs:
            raise ValueError(f"spec {n_pods}x{n_data} on {n_procs} procs: "
                             "cells must split evenly across the gang")
        worker_args = ["--mode", "bench",
                       "--n-pods", str(n_pods), "--n-data", str(n_data),
                       "--n-envs", str(n_envs), "--iters", str(iters),
                       "--repeats", str(repeats),
                       "--scan-chunk", str(scan_chunk),
                       "--update-interval", str(update_interval)]
        if compress:
            worker_args.append("--compress")
        if overlap:
            worker_args.append("--overlap")
        out = mp.launch(worker_args, n_procs=n_procs,
                        devices_per_proc=n_cells // n_procs)
        kv = mp.parse_kv(out[0])
        points.append({
            "backend": "wallclock", "shards": n_data, "pods": n_pods,
            "compressed": bool(compress), "overlapped": bool(overlap),
            "n_procs": n_procs, "update_interval": update_interval,
            "n_envs": n_envs, "platform": kv["PLATFORM"],
            "env_steps_per_s": round(float(kv["STEPS_PER_S"]), 2),
            "repeats": int(kv.get("REPEATS", repeats)),
            "rel_spread": round(float(kv.get("REL_SPREAD", 0.0)), 4),
        })
    return points


def assert_uniform_n_envs(points):
    """Every point of one emitted BENCH_fig10.json must share its global
    env count: the planner ranks these points against each other, which
    is only a like-for-like comparison when each point runs the same
    workload.  A sweep accidentally mixing env counts (e.g. a wall-clock
    arm defaulting differently from the emulated arms) must fail the
    emit, not silently skew the plan."""
    counts = {p.get("n_envs") for p in points}
    if len(counts) > 1:
        raise ValueError(
            f"BENCH_fig10 points mix n_envs={sorted(counts)}: every point "
            "of one emitted sweep must run the same global env count — "
            "pass one n_envs through all arms (benchmarks/run.py)")
    return points


def realize_plan(plan, iters=120):
    """Measured env-steps/s of a planner-chosen config — in-process when
    the plan needs no mesh, else in a forced-device subprocess (the
    ``--_plan-worker`` mode) so the device count is set before jax
    initializes."""
    if plan.n_devices <= 1:
        from benchmarks.fig9_fanout import plan_throughput
        return plan_throughput(plan, iters=iters)
    spec = (f"{plan.backend},{plan.n_pods},{plan.n_data},"
            f"{plan.publish_interval},{plan.max_staleness},"
            f"{int(plan.compress_pod_reduce)}")
    return _run_worker(["--_plan-worker", spec], plan.n_devices,
                       n_envs=plan.n_envs, iters=iters)[0]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", default="",
                    help="comma-separated shard counts, e.g. 1,2,4 — "
                         "benchmarks the ShardedExecutor per count")
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--_sharded-worker", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--_pod-worker", default="",
                    help=argparse.SUPPRESS)   # "n_pods,n_data,compress01"
    ap.add_argument("--_plan-worker", default="",
                    help=argparse.SUPPRESS)
    # "backend,n_pods,n_data,publish_interval,max_staleness,compress01"
    args = ap.parse_args()
    if args._sharded_worker:
        t, spread = sharded_throughput(args._sharded_worker,
                                       n_envs=args.n_envs,
                                       iters=args.iters)
        print(f"STEPS_PER_S={t:.2f}")
        print(f"REL_SPREAD={spread:.4f}")
    elif args._pod_worker:
        p, d, c = (int(x) for x in args._pod_worker.split(","))
        t, spread = pod_sharded_throughput(p, d, bool(c), n_envs=args.n_envs,
                                           iters=args.iters)
        print(f"STEPS_PER_S={t:.2f}")
        print(f"REL_SPREAD={spread:.4f}")
    elif args._plan_worker:
        from benchmarks.fig9_fanout import _make_runtime_executor, _steps_per_s
        backend, p, d, pi, ms, c = args._plan_worker.split(",")
        ex = _make_runtime_executor(
            backend, args.n_envs, int(d), int(pi), int(ms),
            pods=int(p) if int(p) > 1 else 0, compress=bool(int(c)))
        print(f"STEPS_PER_S={_steps_per_s(ex, iters=args.iters):.2f}")
    elif args.shards:
        run_shard_sweep([int(x) for x in args.shards.split(",")])
    else:
        run()
