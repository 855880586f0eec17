"""Quickstart: the paper end-to-end on a laptop — parallel actors +
parallel learners + K-ary-sum-tree prioritized replay, DQN on CartPole,
through the executor API (runtime/executors.py).

    PYTHONPATH=src python examples/quickstart.py [--iterations 3000]

    # sharded runtime: 4 replay/learner shards on forced host devices
    PYTHONPATH=src python examples/quickstart.py --shards 4

    # async runtime: actors act on a 4-iteration-delayed parameter copy
    PYTHONPATH=src python examples/quickstart.py --executor async \\
        --publish-interval 4

    # sharded async: staggered shard clocks + staleness-weighted reduce
    PYTHONPATH=src python examples/quickstart.py --executor async \\
        --shards 4 --publish-interval 4 --max-staleness 1

    # pod scale: 2×2 (pod × data) mesh, gradients reduce f32 inside a
    # pod and cross pods int8-EF-compressed (DESIGN.md §7)
    PYTHONPATH=src python examples/quickstart.py --pods 2 --shards 2 \\
        --compress-pod-reduce

    # planner-selected runtime (DESIGN.md §8): run the config the DSE
    # planner chose from measured throughput — first
    #   PYTHONPATH=src python -m benchmarks.run --emit-json out/ [--smoke]
    # then train straight from the emitted plan:
    PYTHONPATH=src python examples/quickstart.py --plan out/BENCH_plan.json
"""

import argparse
import functools
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan", default=None, metavar="BENCH_plan.json",
                    help="instantiate the executor/mesh a "
                         "runtime.planner plan selected (overrides "
                         "--shards/--pods/--executor/--publish-interval/"
                         "--max-staleness/--n-envs/--update-interval)")
    ap.add_argument("--iterations", type=int, default=3000)
    ap.add_argument("--n-envs", type=int, default=8, help="parallel actors")
    ap.add_argument("--fanout", type=int, default=128,
                    help="sum-tree K (paper Fig. 9 sweep)")
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla",
                    help="TreeOps backend for buffer ops")
    ap.add_argument("--update-interval", type=int, default=1,
                    help="env steps per learn (paper ratio)")
    ap.add_argument("--shards", type=int, default=0,
                    help="run the ShardedExecutor over this many "
                         "host-platform device shards (0 = fused); with "
                         "--pods this is the per-pod data-axis extent")
    ap.add_argument("--pods", type=int, default=0,
                    help="add a pod axis: a (pods × shards) two-axis mesh "
                         "(DESIGN.md §7)")
    ap.add_argument("--compress-pod-reduce", action="store_true",
                    help="int8 error-feedback compressed gradient reduce "
                         "across the pod axis (needs --pods)")
    ap.add_argument("--bf16-intra-pod", action="store_true",
                    help="cast the intra-pod (fast-axis) gradient reduce "
                         "to bf16 on the wire (needs --shards); the "
                         "injected error is the compress_error_norm "
                         "metric")
    ap.add_argument("--eager-replay", action="store_true",
                    help="disable the lazy-writing replay transactions "
                         "(three tree-propagation passes per iteration "
                         "instead of one — the pre-optimization baseline)")
    ap.add_argument("--executor", choices=("sync", "async"), default="sync",
                    help="async = actors act on a delayed parameter copy "
                         "(AsyncExecutor, DESIGN.md §5)")
    ap.add_argument("--publish-interval", type=int, default=4,
                    help="iterations between actor-copy republishes "
                         "(async executor; 1 = synchronous semantics)")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="drop a shard from the gradient reduce once its "
                         "acting copy ages past this many iterations "
                         "(sharded async executor)")
    args = ap.parse_args()

    plan = None
    if args.plan:
        # planner + plan loading are jax-free on purpose: the forced
        # device count must be known before the first jax import
        from repro.runtime.planner import load_plan

        plan = load_plan(args.plan)
        print(f"plan: {plan.describe()}")

    if args.pods and not args.shards:
        args.shards = 1                       # pods alone: P×1 mesh
    if args.compress_pod_reduce and not args.pods:
        ap.error("--compress-pod-reduce needs --pods (the compressed leg "
                 "crosses the pod axis)")
    if args.bf16_intra_pod and not args.shards and not args.plan:
        ap.error("--bf16-intra-pod needs --shards or a sharded --plan "
                 "(the fused path has no cross-shard reduce to cast)")
    n_devices = (plan.n_devices if plan
                 else args.shards * max(1, args.pods))
    if n_devices > 1:
        # must be set before the first jax import; append so a user's
        # existing XLA_FLAGS are kept
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = f"{existing} {flag}".strip()

    import jax
    import jax.numpy as jnp

    from repro import compile_cache
    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.core.distributed import (ShardedPrioritizedReplay,
                                        ShardedReplayConfig)
    from repro.core.replay import PrioritizedReplay, ReplayConfig
    from repro.envs.classic import make_vec
    from repro.launch.mesh import data_mesh, pod_data_mesh
    from repro.runtime.executors import (AsyncExecutor, FusedExecutor,
                                         ShardedExecutor,
                                         executor_from_plan)
    from repro.runtime.loop import LoopConfig

    compile_cache.enable()
    env_fn = functools.partial(make_vec, "cartpole")
    spec, _, _ = env_fn(1)
    agent = make_dqn(spec, DQNConfig(double_q=True))
    example = {
        "obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "action": jnp.zeros((), jnp.int32),
        "reward": jnp.zeros(()),
        "next_obs": jnp.zeros((spec.obs_dim,), jnp.float32),
        "done": jnp.zeros(()),
    }
    cfg = LoopConfig(batch_size=64, warmup=500, epsilon=0.15,
                     update_interval=args.update_interval,
                     lazy_replay=not args.eager_replay)
    intra_pod_dtype = "bf16" if args.bf16_intra_pod else None

    if plan:
        ex = executor_from_plan(plan, agent, env_fn, cfg, example,
                                fanout=args.fanout,
                                tree_backend=args.backend,
                                intra_pod_dtype=intra_pod_dtype)
        print(f"planner-selected {plan.backend} executor on "
              f"{plan.n_devices} device(s), {plan.n_envs} envs "
              f"(predicted {plan.predicted_env_steps_per_s:,.0f} "
              "env-steps/s)")
    elif args.shards:
        if args.pods:
            mesh = pod_data_mesh(args.pods, args.shards)
            axis_names = ("pod", "data")
        else:
            mesh = data_mesh(args.shards)
            axis_names = ("data",)
        n_cells = args.shards * max(1, args.pods)
        replay = ShardedPrioritizedReplay(
            ShardedReplayConfig(capacity_per_shard=50_000 // n_cells,
                                fanout=args.fanout, backend=args.backend,
                                axis_names=axis_names),
            example)
        mesh_desc = (f"{args.pods}×{args.shards} pod×data cells"
                     if args.pods else f"{args.shards} shards")
        fast_dtype = "bf16" if args.bf16_intra_pod else "f32"
        reduce_desc = (f"{fast_dtype} intra-pod + int8-EF cross-pod"
                       if args.compress_pod_reduce
                       else f"{fast_dtype} pmean")
        if args.executor == "async":
            ex = AsyncExecutor(agent, replay, env_fn, cfg, args.n_envs,
                               publish_interval=args.publish_interval,
                               max_staleness=args.max_staleness, mesh=mesh,
                               compress_pod_reduce=args.compress_pod_reduce,
                               intra_pod_dtype=intra_pod_dtype)
            print(f"async sharded executor: {mesh_desc} × "
                  f"{ex.n_envs_local} envs, publish every "
                  f"{args.publish_interval} iters, max staleness "
                  f"{args.max_staleness}, reduce {reduce_desc}")
        else:
            ex = ShardedExecutor(agent, replay, env_fn, cfg, args.n_envs,
                                 mesh,
                                 compress_pod_reduce=args.compress_pod_reduce,
                                 intra_pod_dtype=intra_pod_dtype)
            print(f"sharded executor: {mesh_desc} × "
                  f"{ex.n_envs_local} envs, batch/shard "
                  f"{cfg.batch_size // n_cells}, reduce {reduce_desc}")
    else:
        replay = PrioritizedReplay(
            ReplayConfig(capacity=50_000, fanout=args.fanout,
                         backend=args.backend), example)
        if args.executor == "async":
            ex = AsyncExecutor(agent, replay, env_fn, cfg, args.n_envs,
                               publish_interval=args.publish_interval)
            print("async fused executor: actors on a copy republished "
                  f"every {args.publish_interval} iters")
        else:
            ex = FusedExecutor(agent, replay, env_fn, cfg, args.n_envs)
            print("fused executor (single jit program)")
    print(f"ratio schedule: {ex.schedule} "
          f"(realized {ex.schedule.realized_ratio:.1f} env steps per learn)")

    state, hist = ex.train(args.iterations, jax.random.PRNGKey(0),
                           log_every=256)
    print("\nfinal mean episode return: "
          f"{float(hist['mean_episode_return'][-1]):.1f} "
          "(CartPole solved ≈ 475; random ≈ 10)")


if __name__ == "__main__":
    main()
