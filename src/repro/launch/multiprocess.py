"""Wall-clock multi-process launcher (DESIGN.md §10).

Everything else in this repo emulates a device mesh inside one process
(``--xla_force_host_platform_device_count``), which serializes the
"parallel" shards on the host and makes the fig10 scaling curve a
simulation.  This module launches a real gang: N worker processes, each
owning its own XLA client with ``devices_per_proc`` forced host devices,
joined into one multi-controller SPMD runtime by
``core.distributed.initialize_distributed`` (gloo collectives on CPU).
After the handshake ``jax.devices()`` spans the whole gang in process
order, so the existing ``launch.mesh`` constructors and the shard_map
executors run unchanged — each process executes its addressable mesh
cells and the gradient reduce crosses real process boundaries.

Parent side (``launch``): picks a free coordinator port, spawns
``python -m repro.launch.multiprocess`` once per process id with
per-worker ``XLA_FLAGS``/``PYTHONPATH`` env, streams and collects
stdout, and raises with the failing worker's tail on non-zero exit.
Results travel as ``KEY=VALUE`` lines on process 0's stdout
(``parse_kv``) — the same convention as fig10's emulated pod workers.

Worker side (``main``): initializes the distributed runtime, then runs
one of three workloads:

  * ``--mode bench`` — DQN/CartPole through ``FusedExecutor`` (1 total
    device) or ``ShardedExecutor`` (data or pod×data mesh over the
    gang's global devices, optionally int8-compressed and/or overlapped
    cross-pod reduce), timed median-of-``--repeats`` with ``rel_spread``
    — the wall-clock arm of benchmarks/fig10_scalability.py.  With
    ``--publish-interval P > 0`` the async double buffer is republished
    *externally*: between chunks the fresh params make a real
    device→host→device round trip (``external_publish``) instead of the
    in-program copy.
  * ``--mode fused`` — the degenerate single-process launch: the exact
    ``FusedExecutor.train`` program, printing final metrics and a
    parameter checksum.  Bit-exact against the same executor run
    in-process (tests/test_multiprocess.py): the distributed runtime at
    N=1 must be a no-op.
  * ``--mode equiv`` — 2-process reducer equivalence: the overlapped
    and barrier cross-pod reduces driven over the same per-pod gradient
    streams through real cross-process collectives; process 0 prints
    the shift-identity and telescoping errors
    (tests/test_distributed.py).

Profile traces segment per chunk by the executor's own step marker
(``Executor.run_chunk``'s ``run_chunk`` span).

**Replay-service gang** (DESIGN.md §11): ``launch_service`` spawns a
second kind of gang — one ``--mode replay-server`` process hosting the
sharded rate-limited ``ReplayService``, N ``--mode service-actor``
writer processes and one ``--mode service-learner`` sampler process.
These roles do NOT join ``jax.distributed``: each owns an independent
single-device jax runtime and they meet only at the service's TCP
boundary (append / sample / priority write-back / param channel), so an
actor crash can never wedge a collective.  Results ride the same
``KEY=VALUE`` stdout protocol; the server reports the rate limiter's
realized samples-per-insert ratio and its tolerance band.  With
``restart_learner_after`` the learner exits mid-run after checkpointing
and a fresh learner process resumes from the checkpoint against the
still-live service — actors park in writer backpressure for the gap
(the rate limiter, not a barrier, holds the fleet).
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

HANDSHAKE_TIMEOUT_S = 60.0


# -- parent side -------------------------------------------------------------


def free_port() -> int:
    """A port the coordinator can bind (raced, but single-host tests and
    benchmarks re-launch on collision rather than coordinate)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _src_root() -> str:
    # .../src/repro/launch/multiprocess.py → .../src
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def worker_env(devices_per_proc: int = 1,
               platform: Optional[str] = "cpu") -> Dict[str, str]:
    """Child env: an import path that reaches ``repro`` regardless of the
    parent's cwd, and the child's JAX platform.

    ``platform="cpu"`` pins the child to the host with a forced
    per-process device count (set before any jax import — the whole
    reason the launcher is a separate process): the role of a CPU
    emulation.  ``platform=None`` leaves the choice to JAX, so the child
    takes the accelerator when one is present — unless this process
    already holds it (one process per chip), in which case the child is
    pinned to the CPU as well."""
    env = os.environ.copy()
    if platform is None and _holds_accelerator():
        platform = "cpu"
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices_per_proc}")
    path = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = _src_root() + (os.pathsep + path if path else "")
    return env


def _holds_accelerator() -> bool:
    """True when this process has started a JAX backend other than the
    CPU — it then owns the chip until it exits."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and xla_bridge.get_backend().platform != "cpu")


def print_platform() -> None:
    """Every gang role reports the platform its JAX runs on."""
    import jax

    print(f"PLATFORM={jax.devices()[0].platform}", flush=True)


def launch(
    worker_args: Sequence[str],
    n_procs: int,
    devices_per_proc: int = 1,
    coordinator: Optional[str] = None,
    timeout_s: float = 900.0,
    handshake_timeout_s: float = HANDSHAKE_TIMEOUT_S,
) -> List[str]:
    """Spawn the full ``n_procs`` gang and return per-process stdout
    (index = process id).  Raises ``RuntimeError`` with the failing
    worker's output tail if any exits non-zero or overruns
    ``timeout_s``."""
    if n_procs < 1:
        raise ValueError(f"n_procs={n_procs}: need ≥ 1")
    coordinator = coordinator or f"127.0.0.1:{free_port()}"
    # the wall-clock gang is a CPU emulation: gloo collectives between
    # host processes
    env = worker_env(devices_per_proc, platform="cpu")
    procs = []
    for pid in range(n_procs):
        cmd = [sys.executable, "-m", "repro.launch.multiprocess",
               "--coordinator", coordinator,
               "--n-procs", str(n_procs),
               "--process-id", str(pid),
               "--handshake-timeout", str(handshake_timeout_s),
               *worker_args]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs: List[str] = [""] * n_procs
    deadline = time.monotonic() + timeout_s
    failed = None
    for pid, p in enumerate(procs):
        left = max(1.0, deadline - time.monotonic())
        try:
            outs[pid], _ = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            outs[pid], _ = p.communicate()
            failed = failed or (pid, "timeout")
        if p.returncode not in (0, None) and failed is None:
            failed = (pid, f"exit code {p.returncode}")
    if failed is not None:
        # one worker down wedges the rest at the next collective — kill
        # the whole gang before reporting
        for p in procs:
            if p.poll() is None:
                p.kill()
        pid, why = failed
        tail = "\n".join(outs[pid].splitlines()[-25:])
        raise RuntimeError(
            f"wall-clock worker {pid}/{n_procs} failed ({why}); output "
            f"tail:\n{tail}")
    return outs


def parse_kv(text: str) -> Dict[str, str]:
    """The ``KEY=VALUE`` result lines a worker prints (keys are
    UPPER_SNAKE by convention; later lines win)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip()
        if "=" in line and line.split("=", 1)[0].replace("_", "").isupper():
            k, v = line.split("=", 1)
            out[k] = v
    return out


# -- replay-service gang (parent side) ---------------------------------------


def _wait_for_server(port: int, proc: subprocess.Popen,
                     timeout_s: float = 90.0) -> None:
    """Poll the service port until it accepts; fail fast (with the
    server's output tail) if the server process dies during startup."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            out, _ = proc.communicate()
            tail = "\n".join(out.splitlines()[-25:])
            raise RuntimeError(
                f"replay server exited during startup (code "
                f"{proc.returncode}); output tail:\n{tail}")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1.0):
                return
        except OSError:
            time.sleep(0.1)
    proc.kill()
    raise RuntimeError(
        f"replay server did not open port {port} within {timeout_s:.0f}s")


def launch_service(
    n_actors: int = 2,
    *,
    n_shards: int = 1,
    samples_per_insert: float = 16.0,
    batch_size: int = 64,
    warmup: int = 512,
    learn_steps: int = 1200,
    n_envs: int = 8,
    actor_chunk: int = 8,
    capacity_per_shard: int = 20_000,
    publish_every: int = 16,
    epsilon: float = 0.2,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    restart_learner_after: Optional[int] = None,
    restart_server_after: Optional[int] = None,
    snapshot_dir: Optional[str] = None,
    snapshot_every_appends: int = 0,
    retry_deadline: float = 180.0,
    timeout_s: float = 900.0,
) -> Dict[str, Dict[str, str]]:
    """Spawn the replay-service gang: 1 server + ``n_actors`` writers +
    1 learner, every role its own process with an independent jax
    runtime, meeting only at the service's TCP boundary.  Returns the
    parsed ``KEY=VALUE`` results per role (``server``, ``actor-<i>``,
    ``learner``, plus ``learner-0`` for the pre-restart learner when
    ``restart_learner_after`` is set, and ``server-0`` for the crashed
    server when ``restart_server_after`` is set).

    With ``restart_learner_after`` the first learner process checkpoints
    and exits after that many learn steps *without* stopping the service
    — actors park in writer backpressure — and a second learner process
    resumes from the checkpoint (``--resume``) and trains to completion:
    the elastic-restart drill of DESIGN.md §4.5 against a live service.

    With ``restart_server_after`` the *server* is the casualty
    (DESIGN.md §14): a hard FaultPlan kills it with os._exit(42) when
    its Nth append arrives, while ``snapshot_every_appends=1`` has been
    giving durable acks all along.  Clients park in reconnect backoff,
    a fresh server process restores the latest snapshot onto the same
    port, and training runs through to criterion — with the per-writer
    applied counters provably equal to the clients' acked counts."""
    if n_actors < 1:
        raise ValueError(f"n_actors={n_actors}: need ≥ 1")
    if restart_learner_after is not None and not (ckpt_dir and ckpt_every):
        raise ValueError("restart_learner_after requires ckpt_dir and "
                         "ckpt_every (the resumed learner restores from "
                         "the checkpoint directory)")
    if restart_server_after is not None and not (
            snapshot_dir and snapshot_every_appends):
        raise ValueError("restart_server_after requires snapshot_dir and "
                         "snapshot_every_appends (the restarted server "
                         "restores from the shard snapshots)")
    # the learner does the SGD — the accelerator side of the paper's
    # split — and takes the chip when one is present; the server and the
    # actors are host roles, pinned to the CPU by name
    host_env, learner_env = worker_env(1, "cpu"), worker_env(1, None)
    port = free_port()
    deadline = time.monotonic() + timeout_s

    def spawn(role_args: List[str], env=host_env) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "repro.launch.multiprocess", *role_args]
        return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    common = ["--serve-port", str(port), "--batch-size", str(batch_size),
              "--seed", str(seed),
              "--retry-deadline", str(retry_deadline)]
    # the admission window must absorb one full gang burst: every actor
    # can land a whole rollout chunk between two learner samples
    burst = n_actors * actor_chunk * n_envs
    server_args = ["--mode", "replay-server", *common,
                   "--n-shards", str(n_shards),
                   "--spi", str(samples_per_insert),
                   "--warmup", str(warmup),
                   "--capacity-per-shard", str(capacity_per_shard),
                   "--insert-burst", str(burst),
                   "--serve-timeout", str(timeout_s)]
    if snapshot_dir:
        server_args += ["--snapshot-dir", snapshot_dir,
                        "--snapshot-every-appends",
                        str(snapshot_every_appends)]
    first_server_args = list(server_args)
    if restart_server_after is not None:
        first_server_args += [
            "--fault-plan",
            f"crash_on_op=append:{restart_server_after},hard=1"]
    procs: Dict[str, subprocess.Popen] = {}
    procs["server"] = spawn(first_server_args)
    try:
        _wait_for_server(port, procs["server"],
                         timeout_s=min(90.0, timeout_s))
        for a in range(n_actors):
            procs[f"actor-{a}"] = spawn(
                ["--mode", "service-actor", *common,
                 "--actor-id", str(a),
                 "--n-envs", str(n_envs),
                 "--actor-chunk", str(actor_chunk),
                 "--epsilon", str(epsilon)])
        learner_args = ["--mode", "service-learner", *common,
                        "--n-envs", str(n_envs),
                        "--learn-steps", str(learn_steps),
                        "--publish-every", str(publish_every)]
        if ckpt_dir:
            learner_args += ["--ckpt-dir", ckpt_dir,
                             "--ckpt-every", str(ckpt_every)]
        if restart_learner_after is not None:
            first = spawn([*learner_args,
                           "--exit-after", str(restart_learner_after)],
                          learner_env)
            procs["learner-0"] = first
            first.wait(timeout=max(1.0, deadline - time.monotonic()))
            if first.returncode != 0:
                out, _ = first.communicate()
                tail = "\n".join(out.splitlines()[-25:])
                raise RuntimeError(
                    f"pre-restart learner failed (code {first.returncode}); "
                    f"output tail:\n{tail}")
            procs["learner"] = spawn([*learner_args, "--resume"],
                                     learner_env)
        else:
            procs["learner"] = spawn(learner_args, learner_env)
        if restart_server_after is not None:
            from repro.service.faults import CRASH_EXIT_CODE
            first_server = procs.pop("server")
            procs["server-0"] = first_server
            first_server.wait(timeout=max(1.0, deadline - time.monotonic()))
            if first_server.returncode != CRASH_EXIT_CODE:
                out, _ = first_server.communicate()
                tail = "\n".join(out.splitlines()[-25:])
                raise RuntimeError(
                    f"server did not crash as planned (code "
                    f"{first_server.returncode}, expected "
                    f"{CRASH_EXIT_CODE}); output tail:\n{tail}")
            # actors and learner are now parked in reconnect backoff;
            # the replacement restores the snapshot onto the SAME port
            # (SO_REUSEADDR) so nobody needs re-addressing
            procs["server"] = spawn([*server_args, "--restore-server"])
            _wait_for_server(port, procs["server"],
                             timeout_s=min(90.0, timeout_s))
    except Exception:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        raise

    expected_codes = {"server-0": {0}}
    if restart_server_after is not None:
        from repro.service.faults import CRASH_EXIT_CODE
        expected_codes["server-0"] = {CRASH_EXIT_CODE}
    outs: Dict[str, str] = {}
    failed = None
    for name, p in procs.items():
        left = max(1.0, deadline - time.monotonic())
        try:
            outs[name], _ = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            p.kill()
            outs[name], _ = p.communicate()
            failed = failed or (name, "timeout")
        if (p.returncode not in (0, None) and failed is None
                and p.returncode not in expected_codes.get(name, ())):
            failed = (name, f"exit code {p.returncode}")
    if failed is not None:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        name, why = failed
        tail = "\n".join(outs.get(name, "").splitlines()[-25:])
        raise RuntimeError(
            f"replay-service worker {name} failed ({why}); output "
            f"tail:\n{tail}")
    return {name: parse_kv(text) for name, text in outs.items()}


# -- worker side -------------------------------------------------------------


def _median_spread(samples: Sequence[float]):
    """(median, (max−min)/median) — the rel_spread convention of
    benchmarks/timing.py, inlined because ``benchmarks`` is not
    importable from ``src``."""
    xs = sorted(samples)
    n = len(xs)
    med = (xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2]))
    spread = (xs[-1] - xs[0]) / med if med else 0.0
    return med, spread


def _dqn_cartpole(n_envs_local_hint: int):
    """The benchmark workload everything wall-clock measures: DQN on
    vectorized CartPole (matches fig10's emulated arms)."""
    import functools

    import jax.numpy as jnp

    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.envs.classic import make_vec

    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    agent = make_dqn(spec, DQNConfig())
    example = {
        "obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "done": jnp.zeros(()),
    }
    del n_envs_local_hint
    return env_fn, spec, agent, example


def _build_executor(args):
    import jax

    from repro.core.distributed import ShardedPrioritizedReplay, \
        ShardedReplayConfig
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.launch.mesh import data_mesh, pod_data_mesh
    from repro.runtime.executors import FusedExecutor, ShardedExecutor
    from repro.runtime.loop import LoopConfig

    env_fn, spec, agent, example = _dqn_cartpole(args.n_envs)
    cfg = LoopConfig(batch_size=64, warmup=64, epsilon=0.1,
                     update_interval=args.update_interval)
    n_cells = args.n_pods * args.n_data
    if n_cells != jax.device_count():
        raise RuntimeError(
            f"mesh {args.n_pods}x{args.n_data} wants {n_cells} cells but "
            f"the gang exposes {jax.device_count()} global devices "
            f"({jax.process_count()} procs × "
            f"{len(jax.local_devices())} local)")
    external = args.publish_interval > 0
    if n_cells == 1:
        replay = PrioritizedReplay(
            ReplayConfig(capacity=50_000, fanout=128), example)
        return FusedExecutor(agent, replay, env_fn, cfg, args.n_envs,
                             scan_chunk=args.scan_chunk,
                             publish_interval=args.publish_interval,
                             external_publish=external)
    if args.n_pods > 1:
        mesh, axes = pod_data_mesh(args.n_pods, args.n_data), ("pod", "data")
    else:
        mesh, axes = data_mesh(args.n_data), ("data",)
    replay = ShardedPrioritizedReplay(
        ShardedReplayConfig(capacity_per_shard=50_000 // n_cells,
                            fanout=128, axis_names=axes), example)
    return ShardedExecutor(agent, replay, env_fn, cfg, args.n_envs, mesh,
                           scan_chunk=args.scan_chunk,
                           publish_interval=args.publish_interval,
                           compress_pod_reduce=args.compress,
                           overlap_pod_reduce=args.overlap,
                           external_publish=external)


def _publish_host_roundtrip(ex, state):
    """The real device→host→device parameter publish of the wall-clock
    async mode: fetch the fresh learner params to the host (a true D2H
    transfer — ``jax.device_get`` materializes numpy), then rebuild the
    per-shard acting copies and zero the ages.  Replaces the in-program
    ``jnp.where`` republish (``make_step(external_publish=True)``)."""
    import jax
    import numpy as np

    host = jax.device_get(ex.agent.params_for_acting(state.agent))

    def republish(old, fresh):
        fresh = np.asarray(fresh)
        if old.shape == fresh.shape:        # fused path: plain put
            return jax.device_put(fresh, old.sharding)
        # sharded path: leading shard dim — broadcast the host copy into
        # every shard's slot of the global array
        wide = np.broadcast_to(fresh[None], old.shape)
        return jax.make_array_from_callback(
            old.shape, old.sharding, lambda idx: wide[idx])

    actor_params = jax.tree.map(republish, state.actor_params, host)
    age = state.params_age
    zero = np.zeros(age.shape, dtype=np.int32)
    params_age = jax.make_array_from_callback(
        age.shape, age.sharding, lambda idx: zero[idx])
    return state._replace(actor_params=actor_params, params_age=params_age)


def _bench_worker(args):
    import jax

    ex = _build_executor(args)
    pid = jax.process_index()
    publish = args.publish_interval

    def run_iters(state, iters):
        done = 0
        while done < iters:
            length = min(publish or ex.scan_chunk, iters - done)
            state, metrics = ex.run_chunk(state, length)
            if publish:
                state = _publish_host_roundtrip(ex, state)
            done += length
        return state, metrics

    state = ex.init(jax.random.PRNGKey(args.seed))
    # warmup compiles every chunk length the timed loop will use
    state, _ = run_iters(state, args.iters)
    samples = []
    for r in range(args.repeats):
        t0 = time.perf_counter()
        state, metrics = run_iters(state, args.iters)
        jax.block_until_ready(metrics["env_steps"])
        dt = time.perf_counter() - t0
        samples.append(args.n_envs * args.iters / dt)
    med, spread = _median_spread(samples)
    if pid == 0:
        print(f"STEPS_PER_S={med:.2f}")
        print(f"REL_SPREAD={spread:.4f}")
        print(f"REPEATS={args.repeats}")
        print(f"ENV_STEPS={int(jax.device_get(metrics['env_steps'])[-1])}")


def _fused_worker(args):
    import jax

    if jax.process_count() != 1:
        raise RuntimeError("--mode fused is the degenerate single-process "
                           f"launch; got {jax.process_count()} procs")
    ex = _build_executor(args)
    state, hist = ex.train(args.iters, jax.random.PRNGKey(args.seed))
    params = jax.device_get(state.agent.params)
    checksum = 0.0
    for leaf in jax.tree.leaves(params):
        checksum += float(abs(leaf.astype("float64")).sum())
    print(f"FINAL_LOSS={float(hist['loss'][-1])!r}")
    print(f"FINAL_RETURN={float(hist['mean_episode_return'][-1])!r}")
    print(f"ENV_STEPS={int(hist['env_steps'][-1])}")
    print(f"PARAMS_CHECKSUM={checksum!r}")


def _equiv_worker(args):
    """Overlapped vs barrier cross-pod reduce over *real* 2-process
    collectives: same per-pod gradient streams, checked in-program
    (replicated scalar outputs — per-pod intermediates are never pulled
    to the host, which multi-controller mode would reject):

      * shift identity — on a constant stream, overlapped event t
        equals barrier event t−1 bit-exactly;
      * telescoping — on a varying stream the cumulative applied
        difference collapses to ``p_T − pm_T`` (one gradient's pod
        disagreement, not T of them).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.runtime.learner import make_grad_reducer

    if jax.device_count() != 2:
        raise RuntimeError(f"--mode equiv wants a 2-device (pod) gang, "
                           f"got {jax.device_count()}")
    mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("pod",))
    barrier = make_grad_reducer(("pod",), compress_axis="pod")
    overlap = make_grad_reducer(("pod",), compress_axis="pod", overlap=True)
    T = 8

    def program(gc, gs):
        # local views: gc (1, dim), gs (T, 1, dim) — one pod per process
        z = jnp.zeros_like(gc)

        def b_chain(stream):
            ef, outs = z, []
            for g in stream:
                out, ef = barrier(g, None, ef)
                outs.append(out)
            return outs

        def o_chain(stream):
            ef = {"ef": z, "prev_mean": z, "prev_partial": z}
            outs = []
            for g in stream:
                out, ef = overlap(g, None, ef)
                outs.append(out)
            return outs

        const = [gc] * 6
        ob, oo = b_chain(const), o_chain(const)
        shift = jnp.max(jnp.stack(
            [jnp.max(jnp.abs(oo[t] - ob[t - 1])) for t in range(1, 6)]))

        varying = [gs[t] for t in range(T)]
        vb, vo = b_chain(varying), o_chain(varying)
        cum_diff = sum(vo) - sum(vb)
        # n_data = 1 ⇒ the intra-pod partial is the local gradient itself
        tele = jnp.max(jnp.abs(cum_diff - (varying[-1] - vb[-1])))
        return jax.lax.pmax(shift, "pod"), jax.lax.pmax(tele, "pod")

    run = jax.jit(jax.shard_map(
        program, mesh=mesh, in_specs=(P("pod"), P(None, "pod")),
        out_specs=(P(), P()), check_vma=False))

    # identical host-side streams on every process, sharded pod-major
    dim = 16
    rng = np.random.RandomState(args.seed)
    gc_host = rng.randn(2, 1, dim).astype(np.float32)
    gs_host = rng.randn(T, 2, 1, dim).astype(np.float32)

    def gshard(x, spec):
        sh = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    shift, tele = run(gshard(gc_host, P("pod")),
                      gshard(gs_host, P(None, "pod")))
    if jax.process_index() == 0:
        print(f"SHIFT_MAX_ABS_ERR={float(jax.device_get(shift))!r}")
        print(f"TELESCOPE_MAX_ABS_ERR={float(jax.device_get(tele))!r}")


# -- replay-service workers ---------------------------------------------------


def _params_checksum(params) -> float:
    import jax

    checksum = 0.0
    for leaf in jax.tree.leaves(jax.device_get(params)):
        checksum += float(abs(leaf.astype("float64")).sum())
    return checksum


def _replay_server_worker(args):
    """``--mode replay-server``: host the sharded rate-limited service
    until the learner sends stop, then report flow-control stats.  With
    ``--snapshot-dir`` the service snapshots its full state every
    ``--snapshot-every-appends`` applied appends; ``--restore-server``
    resumes from the latest snapshot (the server-restart drill,
    DESIGN.md §14); ``--fault-plan`` arms deterministic wire faults —
    a ``hard=1`` crash plan kills this process with os._exit(42), so
    every print before it must flush."""
    from repro.service import (FaultPlan, RateLimiter, ReplayService,
                               ReplayServiceConfig, serve)

    _, _, _, example = _dqn_cartpole(1)
    spi = args.spi
    # loose gang band: the admission window absorbs the largest single
    # writer burst (a whole actor chunk), not one lockstep loop step
    eb = 2.0 * max(float(args.batch_size), spi * max(1, args.insert_burst))
    limiter = RateLimiter(samples_per_insert=spi,
                          min_size_to_sample=max(1, args.warmup),
                          error_buffer=eb)
    service = ReplayService(
        ReplayServiceConfig(capacity_per_shard=args.capacity_per_shard,
                            n_shards=args.n_shards,
                            fanout=128,
                            seed=args.seed),
        example, rate_limiter=limiter)
    restored_step = None
    if args.snapshot_dir:
        from repro.checkpoint.manager import CheckpointManager
        manager = CheckpointManager(args.snapshot_dir, keep=3)
        if args.restore_server:
            restored_step = service.restore_snapshot(manager)
            if restored_step is None:
                raise RuntimeError("--restore-server: no snapshot under "
                                   f"{args.snapshot_dir}")
            print(f"RESTORED_STEP={restored_step}", flush=True)
        service.attach_snapshots(
            manager, every_appends=max(1, args.snapshot_every_appends))
    fault_plan = (FaultPlan.parse(args.fault_plan)
                  if args.fault_plan else None)
    server, port = serve(service, port=args.serve_port,
                         fault_plan=fault_plan)
    print(f"SERVE_PORT={port}", flush=True)
    deadline = time.monotonic() + args.serve_timeout
    while not service.stopped and time.monotonic() < deadline:
        time.sleep(0.1)
    timed_out = not service.stopped
    service.stop()
    time.sleep(2.0)  # grace: parked clients drain their final replies
    server.shutdown()
    st = service.stats()
    rl = st["rate_limiter"]
    denom = max(1, int(rl["inserts"]) - int(rl["min_size_to_sample"]))
    print(f"INSERTS={rl['inserts']}")
    print(f"SAMPLES={rl['samples']}")
    print(f"CONFIGURED_SPI={spi!r}")
    print(f"REALIZED_SPI={rl['realized_spi']!r}")
    # the band theorem: |realized − spi| ≤ error_buffer/(inserts − min)
    print(f"SPI_TOLERANCE={eb / denom!r}")
    print(f"MEAN_RECENT_RETURN={st['mean_recent_return']!r}")
    print(f"N_RETURNS={st['n_returns']}")
    print("PER_SHARD_COUNT="
          + ",".join(str(c) for c in st["per_shard_count"]))
    print(f"PARAMS_VERSION={st['params_version']}")
    print(f"APPENDS={st['appends']}")
    print(f"DUP_APPENDS={st['dup_appends']}")
    print("WRITER_APPENDS=" + ",".join(
        f"{w}:{n}" for w, n in sorted(st["writer_appends"].items())))
    print(f"SNAPSHOTS={st['snapshots']}")
    if restored_step is not None:
        print(f"RESTORED_STEP={restored_step}")
    if timed_out:
        raise SystemExit("replay server: no stop received within "
                         f"--serve-timeout {args.serve_timeout:.0f}s")


def _service_actor_worker(args):
    """``--mode service-actor``: run the actor program against the
    service — pull params from the channel, push transition chunks
    through rate-limited appends, until the learner stops the service.
    The ε-schedule clocks off the service's *global* insert counter, so
    the fleet's exploration decays as one actor regardless of how many
    writers share the budget."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.loop import (LoopConfig, init_actor_slice,
                                    make_actor_program)
    from repro.service.client import (ReplayClient, RetryPolicy,
                                      wait_for_service)

    env_fn, _, agent, _ = _dqn_cartpole(args.n_envs)
    _, v_reset, v_step = env_fn(args.n_envs)
    cfg = LoopConfig(batch_size=args.batch_size, warmup=args.warmup,
                     epsilon=args.epsilon)
    program = make_actor_program(agent, v_step, cfg, args.n_envs)

    def chunk(agent_state, sl, key, env_steps0):
        def body(carry, t):
            sl, k = carry
            k_next, kk = jax.random.split(k)
            kk = jax.random.fold_in(kk, args.actor_id)  # decorrelate fleet
            k_act, k_env = jax.random.split(kk)
            sl, transitions = program(agent_state, sl, k_act, k_env,
                                      env_steps0 + t * args.n_envs)
            done = transitions["done"] > 0
            finished = jnp.where(done, sl.last_return, jnp.nan)
            return (sl, k_next), (transitions, finished)

        (sl, key), (trans, finished) = jax.lax.scan(
            body, (sl, key), jnp.arange(args.actor_chunk))
        flat = jax.tree.map(
            lambda x: x.reshape((args.actor_chunk * args.n_envs,)
                                + x.shape[2:]), trans)
        return sl, key, flat, finished

    chunk = jax.jit(chunk)

    wait_for_service("127.0.0.1", args.serve_port, timeout=60.0)
    client = ReplayClient("127.0.0.1", args.serve_port,
                          timeout=args.rpc_timeout,
                          retry=RetryPolicy(base=0.1, cap=3.0,
                                            deadline=args.retry_deadline,
                                            seed=args.seed + args.actor_id))
    # the learner publishes v1 before sampling — actors start on a real
    # policy, never on their own uninitialized weights
    out = client.get_params(min_version=1, timeout=120.0)
    agent_state = agent.init(jax.random.PRNGKey(args.seed))
    agent_state = agent.with_acting_params(
        agent_state, jax.tree.map(jnp.asarray, out["params"]))
    have_version = out["version"]

    sl = init_actor_slice(v_reset, jax.random.PRNGKey(args.seed + 1),
                          args.n_envs, shard_id=args.actor_id)
    key = jax.random.PRNGKey(1000 + args.seed + args.actor_id)
    env_steps0 = jnp.zeros((), jnp.int32)
    chunks = transitions = episodes = 0
    while True:
        sl, key, flat, finished = chunk(agent_state, sl, key, env_steps0)
        fin = np.asarray(finished).ravel()
        rets = [float(r) for r in fin[~np.isnan(fin)]]
        episodes += len(rets)
        reply = client.append(f"actor-{args.actor_id}", flat,
                              returns=rets or None,
                              timeout=args.append_timeout)
        if reply.get("stopped"):
            break
        chunks += 1
        transitions += args.actor_chunk * args.n_envs
        env_steps0 = jnp.asarray(int(reply["inserts"]), jnp.int32)
        if reply["params_version"] > have_version:
            try:
                out = client.get_params(min_version=have_version + 1,
                                        timeout=30.0)
            except RuntimeError:
                # graceful degradation (DESIGN.md §14): a restored
                # server's params version can sit briefly below what a
                # pre-crash reply advertised — keep acting on the
                # last-good params; a later reply re-triggers the pull
                continue
            agent_state = agent.with_acting_params(
                agent_state, jax.tree.map(jnp.asarray, out["params"]))
            have_version = out["version"]
    client.close()
    print(f"ACTOR_ID={args.actor_id}")
    print(f"CHUNKS={chunks}")
    print(f"TRANSITIONS={transitions}")
    print(f"EPISODES={episodes}")
    print(f"PARAMS_VERSION={have_version}")
    print(f"RECONNECTS={client.reconnects}")
    print(f"ACKED_APPENDS={client.acked_appends}")
    print(f"DEDUPED_APPENDS={client.deduped_appends}")


def _eval_policy(agent, agent_state, env_fn, n_envs: int, steps: int,
                 seed: int) -> float:
    """Near-greedy rollout of the learned policy (fresh envs, no replay):
    mean return over every episode that finishes in the window, plus the
    censored running return of any env that outlives the whole window —
    a policy good enough to never terminate must not score 0.0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.loop import (LoopConfig, init_actor_slice,
                                    make_actor_program)

    _, v_reset, v_step = env_fn(n_envs)
    cfg = LoopConfig(epsilon=0.01, epsilon_final=0.01)
    program = make_actor_program(agent, v_step, cfg, n_envs)

    def body(sl, k):
        k_act, k_env = jax.random.split(k)
        sl, transitions = program(agent_state, sl, k_act, k_env,
                                  jnp.zeros((), jnp.int32))
        done = transitions["done"] > 0
        return sl, jnp.where(done, sl.last_return, jnp.nan)

    key = jax.random.PRNGKey(seed)
    sl = init_actor_slice(v_reset, jax.random.fold_in(key, 0), n_envs)
    keys = jax.random.split(jax.random.fold_in(key, 1), steps)
    final, fin = jax.jit(lambda s, ks: jax.lax.scan(body, s, ks))(sl, keys)
    fin = np.asarray(fin)                        # (steps, n_envs); NaN = alive
    finished = fin[~np.isnan(fin)]
    # an env with no completed episode in the window (CartPole's 500-step
    # limit exceeds the 250-step eval window, so a strong policy finishes
    # nothing) is scored by its running return — a lower bound, not a 0
    never_done = ~np.any(~np.isnan(fin), axis=0)
    censored = np.asarray(final.episode_return)[never_done]
    rets = np.concatenate([finished, censored])
    return float(rets.mean()) if rets.size else 0.0


def _service_learner_worker(args):
    """``--mode service-learner``: the sampler side — publish params,
    drain rate-limited samples through the learner program, write
    priorities back, checkpoint periodically, and stop the service when
    the learn budget is spent.  With ``--exit-after`` the process
    checkpoints and exits mid-run *without* stopping the service (the
    restart drill); with ``--resume`` it restores the latest checkpoint
    through the elastic reshard path and continues the count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.loop import make_learner_program
    from repro.service.client import (ReplayClient, RetryPolicy,
                                      wait_for_service)

    env_fn, _, agent, _ = _dqn_cartpole(args.n_envs)
    learn = jax.jit(make_learner_program(agent))
    agent_state = agent.init(jax.random.PRNGKey(args.seed))
    step0 = 0
    manager = None
    if args.ckpt_dir:
        from repro.checkpoint.manager import CheckpointManager
        manager = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume:
            from jax.sharding import Mesh, PartitionSpec as P

            from repro.checkpoint.elastic import reshard

            example = {"agent": agent_state,
                       "learn_step": np.zeros((), np.int32)}
            step, restored = manager.restore_latest(example)
            if step is None:
                raise RuntimeError(
                    f"--resume: no checkpoint under {args.ckpt_dir}")
            mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
            specs = {"agent": jax.tree.map(lambda _: P(),
                                           restored["agent"]),
                     "learn_step": None}
            restored = reshard(restored, specs, mesh)
            agent_state = restored["agent"]
            step0 = int(restored["learn_step"])
            print(f"RESUMED_FROM={step0}", flush=True)
    if args.exit_after and manager is None:
        raise RuntimeError("--exit-after requires --ckpt-dir (the resumed "
                           "learner restores from the checkpoint)")

    wait_for_service("127.0.0.1", args.serve_port, timeout=60.0)
    client = ReplayClient("127.0.0.1", args.serve_port,
                          timeout=args.rpc_timeout,
                          retry=RetryPolicy(base=0.1, cap=3.0,
                                            deadline=args.retry_deadline,
                                            seed=args.seed + 1000))
    client.put_params(agent.params_for_acting(agent_state))

    def save(step):
        manager.save(step, {"agent": jax.device_get(agent_state),
                            "learn_step": np.int32(step)})

    learn_step = step0
    last_loss = float("nan")
    while learn_step < args.learn_steps:
        try:
            out = client.sample(args.batch_size, beta=0.4,
                                timeout=args.rpc_timeout)
            if out.get("stopped"):
                break
            agent_state, metrics, td = learn(
                agent_state, jax.tree.map(jnp.asarray, out["items"]),
                jnp.asarray(out["weights"]))
            client.update_priorities(out["sample_id"], np.asarray(td))
            learn_step += 1
            last_loss = float(metrics["loss"])
            if learn_step % args.publish_every == 0:
                client.put_params(agent.params_for_acting(agent_state))
        except ConnectionError:
            # bounded degradation (DESIGN.md §14): the client already
            # spent its full reconnect-retry budget — the service is
            # gone for good.  Checkpoint what we have and exit cleanly
            # instead of hanging the gang.
            if manager is not None:
                save(learn_step)
            client.close()
            print(f"LEARN_STEPS={learn_step}")
            print(f"FINAL_LOSS={last_loss!r}")
            print("SAMPLE_RETRY_EXHAUSTED=1")
            return
        if manager is not None and args.ckpt_every \
                and learn_step % args.ckpt_every == 0:
            save(learn_step)
        if args.exit_after and learn_step - step0 >= args.exit_after \
                and learn_step < args.learn_steps:
            # planned mid-run exit: checkpoint, leave the service up —
            # actors park in writer backpressure until the resumed
            # learner's samples pay the debt back down
            save(learn_step)
            client.close()
            print(f"LEARN_STEPS={learn_step}")
            print("EXITED_EARLY=1")
            return

    client.put_params(agent.params_for_acting(agent_state))
    if manager is not None and args.ckpt_every:
        save(learn_step)
    stats = client.stats()
    eval_ret = _eval_policy(agent, agent_state, env_fn, n_envs=8,
                            steps=250, seed=args.seed + 7)
    client.stop()
    client.close()
    rl = stats.get("rate_limiter", {})
    print(f"LEARN_STEPS={learn_step}")
    print(f"FINAL_LOSS={last_loss!r}")
    print(f"EVAL_RETURN={eval_ret!r}")
    print(f"PARAMS_CHECKSUM={_params_checksum(agent_state.params)!r}")
    print(f"MEAN_RECENT_RETURN={stats['mean_recent_return']!r}")
    print(f"SERVICE_INSERTS={stats['inserts']}")
    print(f"SERVICE_SAMPLES={stats['samples']}")
    print(f"REALIZED_SPI={rl.get('realized_spi', 0.0)!r}")


# -- entry -------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        description="wall-clock multi-process worker (spawned by launch())")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the jax.distributed coordinator "
                         "(process 0 binds it); required for the SPMD "
                         "modes, unused by the replay-service roles")
    ap.add_argument("--n-procs", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--handshake-timeout", type=float,
                    default=HANDSHAKE_TIMEOUT_S)
    ap.add_argument("--mode",
                    choices=("bench", "fused", "equiv", "replay-server",
                             "service-actor", "service-learner"),
                    default="bench")
    ap.add_argument("--n-pods", type=int, default=1)
    ap.add_argument("--n-data", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--publish-interval", type=int, default=0)
    ap.add_argument("--update-interval", type=int, default=1)
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--scan-chunk", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    # replay-service roles (DESIGN.md §11)
    ap.add_argument("--serve-port", type=int, default=0)
    ap.add_argument("--n-shards", type=int, default=1)
    ap.add_argument("--spi", type=float, default=16.0,
                    help="configured samples-per-insert ratio")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--warmup", type=int, default=512)
    ap.add_argument("--capacity-per-shard", type=int, default=20_000)
    ap.add_argument("--insert-burst", type=int, default=64,
                    help="largest single writer append the band absorbs")
    ap.add_argument("--serve-timeout", type=float, default=600.0)
    ap.add_argument("--actor-id", type=int, default=0)
    ap.add_argument("--actor-chunk", type=int, default=8,
                    help="env steps per jitted actor rollout / append")
    ap.add_argument("--epsilon", type=float, default=0.2)
    ap.add_argument("--learn-steps", type=int, default=1200)
    ap.add_argument("--publish-every", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--exit-after", type=int, default=0,
                    help="learner: checkpoint and exit after this many "
                         "learn steps without stopping the service")
    ap.add_argument("--resume", action="store_true",
                    help="learner: restore the latest checkpoint")
    ap.add_argument("--rpc-timeout", type=float, default=300.0)
    ap.add_argument("--append-timeout", type=float, default=240.0)
    ap.add_argument("--retry-deadline", type=float, default=180.0,
                    help="client reconnect-retry budget per call — must "
                         "cover a full server restart (jax import included)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="server: shard-snapshot directory (DESIGN.md §14)")
    ap.add_argument("--snapshot-every-appends", type=int, default=0,
                    help="server: snapshot period in applied appends "
                         "(1 = durable acks, the restart drill setting)")
    ap.add_argument("--restore-server", action="store_true",
                    help="server: restore the latest shard snapshot from "
                         "--snapshot-dir before serving")
    ap.add_argument("--fault-plan", default=None,
                    help="server: FaultPlan.parse spec, e.g. "
                         "'crash_on_op=append:40,hard=1'")
    args = ap.parse_args(argv)

    service_roles = {"replay-server": _replay_server_worker,
                     "service-actor": _service_actor_worker,
                     "service-learner": _service_learner_worker}
    if args.mode in service_roles:
        # service roles never join jax.distributed: independent runtimes
        # meeting only at the TCP boundary (a dead actor cannot wedge a
        # collective — there are none)
        print_platform()
        service_roles[args.mode](args)
        return
    if args.coordinator is None:
        ap.error("--coordinator is required for modes bench/fused/equiv")

    from repro.core.distributed import initialize_distributed

    initialize_distributed(args.coordinator, args.n_procs, args.process_id,
                           timeout_s=args.handshake_timeout)
    print_platform()
    {"bench": _bench_worker,
     "fused": _fused_worker,
     "equiv": _equiv_worker}[args.mode](args)


if __name__ == "__main__":
    main()
