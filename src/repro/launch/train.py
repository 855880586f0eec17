"""Distributed RL training driver (deliverable b/e): the paper's full
pipeline — parallel actors (token MDP), sharded prioritized replay,
parallel learners with the token-Q update — on an arbitrary mesh, with
checkpoint/restart.

On this host it runs real steps with a reduced config:
    PYTHONPATH=src python -m repro.launch.train --arch granite_8b --smoke \
        --steps 50
On a pod, drop --smoke and point --mesh at the production topology
(16x16 or 2x16x16); the same code path lowers — the dry-run proves it
compiles for every assigned arch.

``--plan BENCH_plan.json`` applies a DSE-planner config
(runtime/planner.py, DESIGN.md §8): the planned actor-lane count
becomes ``--n-envs``, the planned device count is forced before jax
initializes, and the planned (pod×)data mesh is installed as the
ambient mesh (``launch.mesh.mesh_from_plan``).  The RL-executor-level
instantiation of a plan lives in ``runtime.executors.
executor_from_plan`` (see examples/quickstart.py --plan).

``--wall-clock N`` (DESIGN.md §10) re-launches this driver as N real
worker processes through ``launch.multiprocess``: the parent spawns the
gang (fresh XLA client per worker, gloo collectives) and each worker
joins the multi-controller runtime via
``core.distributed.initialize_distributed`` before its first jax call.
Workers split ``--n-envs`` evenly, run the same training body on their
own actor streams, and data-parallel-average the parameters across the
gang after every train step — a real device→host→wire→device round
trip, not an in-program copy.  Process 0 owns printing and checkpoints.
Incompatible with ``--plan``/``--mesh`` (those emulate topology inside
one process — the opposite of this mode).
"""

import argparse
import contextlib
import functools
import os
import sys
import time

from repro.compile_cache import CHECKOUT


def _make_param_averager(n_procs: int):
    """Cross-process parameter mean for the wall-clock gang: each worker
    contributes its local params as one slot of a leading-proc-axis
    global array, a shard_map pmean over the ``("proc",)`` mesh reduces
    them over the wire, and the replicated result is pulled back to the
    worker's local device — so the published params really crossed
    device→host→gloo→device, not an XLA alias."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(jax.devices()).reshape(n_procs), ("proc",))
    local_dev = jax.local_devices()[0]

    def pmean(tree):
        # local view of each stacked leaf is this worker's (1, …) slot;
        # drop it so the replicated output has the original leaf shape
        # repro-lint: disable=C202(local one-axis gang mesh, not the pod/data/model training mesh)
        return jax.tree.map(lambda x: jax.lax.pmean(x[0], "proc"), tree)

    reduce_fn = jax.jit(jax.shard_map(
        pmean, mesh=mesh, in_specs=PartitionSpec("proc"),
        out_specs=PartitionSpec(), check_vma=False))

    def to_global(leaf):
        shape = (n_procs,) + leaf.shape
        sharding = NamedSharding(mesh, PartitionSpec("proc"))
        local = jax.device_put(leaf[None], local_dev)
        return jax.make_array_from_single_device_arrays(
            shape, sharding, [local])

    def sync(params):
        stacked = jax.tree.map(to_global, params)
        mean = reduce_fn(stacked)
        host = jax.device_get(mean)   # fully replicated → addressable
        return jax.tree.map(lambda x: jax.device_put(x, local_dev), host)

    return sync


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--mesh", default="host",
                    help="'host' | '16x16' | '2x16x16' (pods need the "
                         "512-device dry-run env)")
    ap.add_argument("--plan", default=None, metavar="BENCH_plan.json",
                    help="apply a runtime.planner plan: planned n_envs, "
                         "forced device count and ambient (pod×)data "
                         "mesh (overrides --n-envs; --mesh must stay "
                         "'host')")
    ap.add_argument("--wall-clock", type=int, default=0, metavar="N",
                    help="launch N real worker processes (multi-"
                         "controller SPMD over gloo) instead of the "
                         "in-process run; params are data-parallel-"
                         "averaged across the gang every step")
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(CHECKOUT, "out", "train_ckpt"),
                    help="checkpoints; a run resumes from the latest one")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    wc_coord = os.environ.get("REPRO_WC_COORD")
    if args.wall_clock and args.wall_clock > 1 and wc_coord is None:
        # parent: spawn the gang re-running this driver, worker env
        # (XLA_FLAGS / PYTHONPATH / coordinator) set per child
        if args.plan or args.mesh != "host":
            ap.error("--wall-clock spawns real processes — drop "
                     "--plan/--mesh (those emulate topology in-process)")
        from repro.launch import multiprocess as mp

        n = args.wall_clock
        coordinator = f"127.0.0.1:{mp.free_port()}"
        argv = list(sys.argv[1:])
        i = argv.index("--wall-clock")
        del argv[i:i + 2]
        env = mp.worker_env(devices_per_proc=1, platform="cpu")  # gloo
        env["REPRO_WC_COORD"] = coordinator
        env["REPRO_WC_NPROCS"] = str(n)
        import subprocess
        procs = []
        for pid in range(n):
            cenv = dict(env, REPRO_WC_PID=str(pid))
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.launch.train", *argv],
                env=cenv, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        rc = 0
        for pid, p in enumerate(procs):
            out, _ = p.communicate()
            for line in out.splitlines():
                print(f"[worker {pid}] {line}")
            rc = rc or p.returncode
        if rc:
            raise SystemExit(rc)
        return

    if wc_coord is not None:
        # worker: join the gang before the first jax call
        from repro.core.distributed import initialize_distributed

        wc_nprocs = int(os.environ["REPRO_WC_NPROCS"])
        wc_pid = int(os.environ["REPRO_WC_PID"])
        initialize_distributed(wc_coord, wc_nprocs, wc_pid)
        if args.n_envs % wc_nprocs:
            ap.error(f"--n-envs {args.n_envs} not divisible by the "
                     f"{wc_nprocs}-process gang")
        args.n_envs //= wc_nprocs
    else:
        wc_nprocs, wc_pid = 1, 0

    plan = None
    if args.plan:
        if args.mesh != "host":
            ap.error("--plan carries its own mesh — drop --mesh")
        # jax-free load: the forced device count must precede jax init
        from repro.runtime.planner import load_plan

        plan = load_plan(args.plan)
        args.n_envs = plan.n_envs
        print(f"plan: {plan.describe()}")
        if plan.n_devices > 1:
            os.environ["XLA_FLAGS"] = (
                f"{os.environ.get('XLA_FLAGS', '')} "
                "--xla_force_host_platform_device_count="
                f"{plan.n_devices}").strip()

    if args.mesh != "host":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    import jax
    import jax.numpy as jnp

    from repro import compile_cache
    from repro.agents import token_dqn
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.envs.token_mdp import TokenMDPSpec, make
    from repro.launch.mesh import (make_production_mesh, mesh_from_plan,
                                   sharding_config)
    from repro.models import backbone
    from repro.models.config import NO_SHARDING
    from repro.optim import adam

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    if plan is not None:
        # the planned (pod×)data mesh becomes the ambient mesh; the
        # token model itself stays unsharded (NO_SHARDING) — the plan's
        # mesh carries the actor/learner data axes, not tensor parallel
        shd = NO_SHARDING
        mesh = mesh_from_plan(plan)
    elif args.mesh == "host":
        shd = NO_SHARDING
        mesh = None
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")
        shd = sharding_config(args.mesh == "2x16x16")

    tcfg = token_dqn.TokenDQNConfig(gamma=0.9, accum=1,
                                    opt=adam.AdamConfig(lr=1e-4))
    key = jax.random.PRNGKey(0)
    state = token_dqn.init_train_state(cfg, tcfg, key)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    mesh_desc = (f"plan:{plan.n_pods}x{plan.n_data}" if plan is not None
                 else args.mesh)
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={mesh_desc} "
          f"platform={jax.devices()[0].platform}")

    mdp = TokenMDPSpec(vocab=cfg.vocab_size)
    reset, step_env, optimal = make(mdp, jax.random.fold_in(key, 1), args.n_envs)
    # per-worker actor streams: decorrelated resets, replicated params
    env_state, obs = reset(jax.random.fold_in(jax.random.fold_in(key, 2),
                                              wc_pid))

    sync_params = None
    if wc_nprocs > 1:
        sync_params = _make_param_averager(wc_nprocs)

    example = {
        "tokens": jnp.zeros((args.seq,), jnp.int32),
        "actions": jnp.zeros((args.seq,), jnp.int32),
        "rewards": jnp.zeros((args.seq,), jnp.float32),
        "dones": jnp.zeros((args.seq,), jnp.float32),
    }
    replay = PrioritizedReplay(ReplayConfig(capacity=8192, fanout=128), example)
    rst = replay.init()

    @jax.jit
    def collect(params, env_state, obs, key):
        def one(carry, i):
            env_state, obs, ctx = carry
            k = jax.random.fold_in(key, i)
            logits = backbone.forward(cfg, shd, params, ctx)[:, -1]
            greedy = jnp.argmax(logits, -1)
            rand = jax.random.randint(k, greedy.shape, 0, cfg.vocab_size)
            act = jnp.where(jax.random.uniform(k, greedy.shape) < 0.1,
                            rand, greedy)
            env_state2, obs2, rew, done = step_env(env_state, act, k)
            ctx2 = jnp.concatenate([ctx[:, 1:], obs2[:, None]], axis=1)
            return (env_state2, obs2, ctx2), (obs, act, rew, done)

        ctx0 = jnp.tile(obs[:, None], (1, 8))
        (env_state, obs, _), (toks, acts, rews, dones) = jax.lax.scan(
            one, (env_state, obs, ctx0), jnp.arange(args.seq))
        return env_state, obs, {
            "tokens": toks.T, "actions": acts.T,
            "rewards": rews.T, "dones": dones.T.astype(jnp.float32)}

    train_step = jax.jit(functools.partial(token_dqn.train_step, cfg, shd, tcfg),
                         donate_argnums=(0,))
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start, state = mgr.restore_latest(state)
    if start is not None:
        print(f"resumed from step {start} (fault-tolerant restart)")

    stack = contextlib.ExitStack()
    if plan is not None and mesh is not None:
        # planned data mesh as the ambient mesh for the training steps
        stack.enter_context(jax.set_mesh(mesh))

    ctx = None
    t0 = time.time()
    for it in range(int(state.step), args.steps):
        key, kc, ks = jax.random.split(key, 3)
        env_state, obs, seg = collect(state.params, env_state, obs, kc)
        rst = replay.insert(rst, seg)
        idx, items, w = replay.sample(rst, ks, args.batch)
        state, metrics, tds = train_step(state, dict(items, is_weights=w))
        rst = replay.update_priorities(rst, idx, tds)
        if sync_params is not None:
            # wall-clock gang: data-parallel parameter average across
            # processes — a real D2H → gloo → H2D round trip per step
            state = state._replace(params=sync_params(state.params))
        if wc_pid == 0 and it % 10 == 0:
            print(f"step {it:4d} loss {float(metrics['loss']):.4f} "
                  f"reward {float(jnp.mean(seg['rewards'])):.3f} "
                  f"(optimal {optimal():.3f})")
        if (args.ckpt_every and it and it % args.ckpt_every == 0
                and wc_pid == 0):
            mgr.save_async(it, state)
    mgr.wait()
    if wc_pid == 0:
        mgr.save(args.steps, state)
    stack.close()
    if wc_pid == 0:
        print(f"trained {args.steps - (start or 0)} steps in "
              f"{time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
