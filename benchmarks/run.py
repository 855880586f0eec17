# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness (deliverable d): one module per paper figure.

    fig8  — parallel framework vs sequential baseline (env-steps/s, speedup)
    fig9  — K-ary sum tree vs binary tree, fanout sweep (per-op µs, speedup)
    fig10 — DQN/DDPG/SAC scalability vs parallel actor lanes
    fig11 — our buffer plugged into a naive trainer (iteration µs, speedup)
    fig12 — DSE profile curves + Eq. 5 solution via the runtime planner
    replay — lazy-vs-eager / fused-vs-split replay-transaction ops/s
    roofline — §Roofline table from the dry-run artifacts (if present)

Run: PYTHONPATH=src python -m benchmarks.run [--only fig9,...]

Machine-readable perf trajectory: ``--emit-json DIR`` writes

    BENCH_fig9.json  — env-steps/s per runtime executor backend
                       (fused + async publish-interval sweep, in-process)
    BENCH_fig10.json — env-steps/s per shard/pod count (1-D data-axis
                       counts and 2-D pod×data points with and without
                       the int8-EF compressed cross-pod reduce; one
                       forced-device subprocess per point)
    BENCH_plan.json  — the runtime config the DSE planner
                       (runtime/planner.py) selected from those points,
                       with predicted vs realized env-steps/s and the
                       Eq. 5 lane curves it solved over
    BENCH_replay.json — replay-transaction ops/s per (backend, eager|
                       lazy, fused|split) arm (benchmarks/replay_micro)
    BENCH_serve.json — replay-service sustained insert/sample rates vs
                       concurrent writer count (benchmarks/fig_serve) —
                       the planner's service-shape inputs
    BENCH_actor.json — actor-serve load generator (benchmarks/fig_actor):
                       sustained requests/s + p50/p99 latency of the
                       continuous-batching inference frontend under N
                       simulated users, with the mid-run param-swap drill

Every point is a median-of-N repeat with its dispersion recorded
(benchmarks/timing.py — the groundwork for a blocking perf gate).

so CI and the roadmap can diff throughput across PRs instead of
eyeballing CSV — the json is validated by ``benchmarks/schema.py`` and
diffed against the committed repo-root baselines by
``benchmarks/compare.py``.  ``--emit-json`` runs only the executor
sweeps (no tree/figure suites) unless ``--only`` also names suites.
``--smoke`` shrinks every sweep to a CI-sized budget (fewer points,
fewer iterations) — same schema, same code paths.
"""

import argparse
import json
import os
import sys
import traceback


def emit_json(out_dir: str, smoke: bool = False,
              wallclock: bool = False) -> None:
    from benchmarks import fig10_scalability, fig_actor, fig_serve, replay_micro
    from repro.runtime import planner

    os.makedirs(out_dir, exist_ok=True)
    replay_micro.emit_json(out_dir, smoke=smoke)
    fig_serve.emit_json(out_dir, smoke=smoke)
    fig_actor.emit_json(out_dir, smoke=smoke)
    prof = planner.profile(smoke=smoke)
    fig10_points = list(prof["fig10_points"])
    if wallclock:
        # the real multi-process gang arm (DESIGN.md §10) — measured at
        # the same global env count as the emulated arms of this run so
        # the uniformity invariant below holds
        n_envs = fig10_points[0]["n_envs"] if fig10_points else 8
        fig10_points += fig10_scalability.wallclock_points(
            n_envs=n_envs, iters=20 if smoke else 40)
    fig10_scalability.assert_uniform_n_envs(fig10_points)
    fig9 = {
        "figure": "fig9",
        "metric": "env_steps_per_s",
        "smoke": smoke,
        "points": prof["fig9_points"],
    }
    fig10 = {
        "figure": "fig10",
        "metric": "env_steps_per_s",
        "smoke": smoke,
        "points": fig10_points,
    }
    for name, payload in ((planner.FIG9_JSON, fig9),
                          (planner.FIG10_JSON, fig10)):
        path = os.path.join(out_dir, name)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {path} ({len(payload['points'])} points)",
              file=sys.stderr)

    serve_points = []
    serve_path = os.path.join(out_dir, fig_serve.SERVE_JSON)
    if os.path.exists(serve_path):
        with open(serve_path) as f:
            serve_points = json.load(f).get("points", [])
    pc = planner.plan(
        prof["fig9_points"], fig10_points,
        serve_points=serve_points,
        actor_curve=prof["actor_curve"],
        learner_curve=prof["learner_curve"],
        source="emit-json")
    realized = fig10_scalability.realize_plan(pc, iters=40 if smoke else 120)
    plan_path = os.path.join(out_dir, planner.PLAN_JSON)
    planner.save_plan(
        pc, plan_path,
        realized_env_steps_per_s=round(realized, 2),
        curves={
            "actor": {str(k): round(v, 2)
                      for k, v in prof["actor_curve"].items()},
            "learner": {str(k): round(v, 2)
                        for k, v in prof["learner_curve"].items()},
        })
    print(f"# wrote {plan_path}: {pc.describe()}", file=sys.stderr)
    print(f"#   realized {realized:,.0f} env-steps/s "
          f"(predicted {pc.predicted_env_steps_per_s:,.0f})",
          file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig9,roofline")
    ap.add_argument("--emit-json", default=None, metavar="DIR",
                    help="write BENCH_fig9.json / BENCH_fig10.json / "
                         "BENCH_plan.json (env-steps/s per executor "
                         "backend and shard/pod count, plus the planner-"
                         "selected config) into DIR")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized budget: fewer sweep points and "
                         "iterations, same schema and code paths")
    ap.add_argument("--wall-clock", action="store_true",
                    help="add the real multi-process gang arm to "
                         "BENCH_fig10.json (launch/multiprocess.py: one "
                         "OS process per worker, gloo collectives)")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()
    failed = []
    if args.emit_json:
        try:
            emit_json(args.emit_json, smoke=args.smoke,
                      wallclock=args.wall_clock)
        except Exception:  # noqa: BLE001 — keep the harness sweeping
            failed.append("emit-json")
            traceback.print_exc()

    if args.only or not args.emit_json:
        from benchmarks import (fig8_baseline, fig9_fanout, fig10_scalability,
                                fig11_plugin, fig12_dse, fig_actor, fig_serve,
                                replay_micro, roofline)
        suites = {
            "fig8": fig8_baseline.run,
            "fig9": fig9_fanout.run,
            "fig10": fig10_scalability.run,
            "fig11": fig11_plugin.run,
            "fig12": fig12_dse.run,
            "replay": replay_micro.run,
            "serve": fig_serve.run,
            "actor": fig_actor.run,
            "roofline": roofline.run,
        }
        chosen = (args.only.split(",") if args.only else list(suites))
        print("name,us_per_call,derived")
        for name in chosen:
            try:
                suites[name](csv=True)
            except Exception:  # noqa: BLE001 — keep the harness sweeping
                failed.append(name)
                traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
