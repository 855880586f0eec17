"""Runtime configuration planner (runtime/planner.py, DESIGN.md §8):
Eq. 5 backward-compat with the 1-D DSE, measured-faster backend
selection, staleness/aliasing feasibility, BENCH json round trips, the
schema/compare CI gates, and plan → executor instantiation."""

import functools
import json
import math
import os
import subprocess
import sys

import jax
import pytest

from repro.runtime import dse, planner
from repro.runtime.loop import LoopConfig, RatioSchedule


def _fig9_point(backend="fused", shards=0, publish_interval=0, n_envs=8,
                steps=1000.0):
    return {"backend": backend, "shards": shards, "pods": 1,
            "publish_interval": publish_interval, "max_staleness": 0,
            "n_envs": n_envs, "env_steps_per_s": steps,
            "speedup_vs_sync": 1.0}


def _fig10_point(shards, pods=1, compressed=False, steps=1000.0, n_envs=16):
    backend = "sharded_pod_data" if pods > 1 else "sharded"
    return {"backend": backend, "shards": shards, "pods": pods,
            "compressed": compressed, "n_envs": n_envs,
            "env_steps_per_s": steps}


# -- Eq. 5 lane split: backward compatibility with the 1-D DSE ---------------


def test_solve_lanes_matches_dse_solve():
    """The planner's lane split IS dse.solve on identical curves — the
    1-D DSE remains a special case of the planner (acceptance
    criterion)."""
    actor = {x: 100.0 * x for x in range(1, 9)}
    learner = {x: 300.0 * x ** 0.8 for x in range(1, 9)}
    for ui in (1.0, 2.0, 4.0):
        a = planner.solve_lanes(actor, learner, total=8, update_interval=ui)
        b = dse.solve(actor, learner, total=8, update_interval=ui)
        assert (a.x_actor, a.x_learner) == (b.x_actor, b.x_learner)
        assert a.actor_throughput == b.actor_throughput
        assert a.ratio_error == b.ratio_error


def test_learn_period_matches_ratio_schedule():
    """planner.learn_period is dependency-free on purpose (a plan must
    be checkable before jax imports) — assert parity with the schedule
    the executors actually realize."""
    for u in (1, 2, 4, 8, 16, 32, 100):
        for e in (1, 2, 4, 8, 16):
            sched = RatioSchedule.from_config(
                LoopConfig(update_interval=u), e)
            assert planner.learn_period(u, e) == sched.period, (u, e)


# -- dse scoring normalization (tie-break bugfix) ----------------------------


def test_backend_selection_not_dominated_by_curve_units():
    """Regression: ranking Eq. 5 solutions across backends used the raw
    ``-(fa + fl)`` sum, so a backend whose json curves happened to be
    recorded in larger units won every comparison on magnitude alone.
    Backend selection must follow ratio fit + env-steps/s, not the
    learner curve's unit."""
    # "good": clean ratio match, modest learner units (batches/s)
    good = ({1: 100.0, 2: 200.0, 4: 400.0},
            {1: 100.0, 2: 200.0, 4: 400.0})
    # "bloated": worse achievable ratio, learner curve in items/s-style
    # huge numbers — the raw sum would dwarf "good"
    bloated = ({1: 100.0, 2: 200.0, 4: 400.0},
               {1: 9.9e6, 2: 9.95e6, 4: 1e7})
    name, res = planner.solve_backend_curves(
        {"good": good, "bloated": bloated}, total=8, update_interval=1.0)
    assert name == "good"
    assert res.ratio_error == pytest.approx(0.0)
    # the old raw tie-break really would have ranked "bloated" first:
    raw_good = res.actor_throughput + res.learner_throughput
    bl = dse.solve(*bloated, total=8, update_interval=1.0)
    raw_bloated = bl.actor_throughput + bl.learner_throughput
    assert raw_bloated > raw_good  # magnitude lies; ratio error doesn't


def test_backend_selection_unit_invariant():
    """Jointly rescaling one backend's curves (a unit change — e.g. a
    json emitted in k-steps/s) must not change which backend wins on
    ratio fit."""
    a = ({1: 100.0, 2: 200.0}, {1: 100.0, 2: 200.0})
    b = ({1: 80.0, 2: 150.0}, {1: 120.0, 2: 130.0})
    base, _ = planner.solve_backend_curves({"a": a, "b": b}, total=4)
    scaled_b = ({k: v * 1024.0 for k, v in b[0].items()},
                {k: v * 1024.0 for k, v in b[1].items()})
    rescaled, _ = planner.solve_backend_curves(
        {"a": a, "b": scaled_b}, total=4)
    # ratio error is scale-free, so the ranking must be identical
    assert base == rescaled == "a"


def test_solve_tiebreak_unit_invariant():
    """The in-solve tie-break must not depend on the learner curve's
    unit: rescaling it by a power of two (lossless in floats) together
    with the target ratio leaves the chosen allocation unchanged."""
    actor = {1: 60.0, 2: 60.0}            # saturated collection
    learner = {1: 2560.0, 2: 5120.0}
    u = 1.0 / 64.0                        # binary-exact target ratio
    base = dse.solve(actor, learner, total=4, update_interval=u)
    scaled = dse.solve(actor, {k: v * 1024.0 for k, v in learner.items()},
                       total=4, update_interval=u / 1024.0)
    assert (base.x_actor, base.x_learner) == (scaled.x_actor,
                                              scaled.x_learner)


def test_relative_score_orders_unit_free():
    res = dse.solve({1: 10.0, 2: 20.0}, {1: 1e6, 2: 2e6}, total=4)
    s = dse.relative_score(res, {1: 10.0, 2: 20.0}, {1: 1e6, 2: 2e6})
    assert s[0] == res.ratio_error
    assert -2.0 <= s[1] <= 0.0            # both terms normalized to ≤ 1


# -- full-config planning ----------------------------------------------------


def test_plan_picks_measured_faster_backend():
    fig9 = [_fig9_point("fused", steps=1000.0),
            _fig9_point("async", publish_interval=2, steps=1400.0)]
    fig10 = [_fig10_point(2, steps=1800.0),
             _fig10_point(2, pods=2, compressed=True, steps=2600.0)]
    pc = planner.plan(fig9, fig10)
    assert pc.backend == "sharded"
    assert (pc.n_pods, pc.n_data) == (2, 2)
    assert pc.compress_pod_reduce
    assert pc.predicted_env_steps_per_s == 2600.0
    assert pc.n_devices == 4

    # without the shard/pod sweep the fastest fig9 point wins
    pc = planner.plan(fig9, [])
    assert pc.backend == "async"
    assert pc.publish_interval == 2


def test_plan_respects_device_budget():
    fig9 = [_fig9_point("fused", steps=1000.0)]
    fig10 = [_fig10_point(4, steps=4000.0)]
    pc = planner.plan(fig9, fig10, max_devices=1)
    assert pc.backend == "fused"          # the 4-shard point needs 4 devices
    pc = planner.plan(fig9, fig10, max_devices=4)
    assert pc.backend == "sharded" and pc.n_data == 4


def test_plan_never_selects_aliasing_rejected_async():
    """A publish_interval sharing a factor with the learn period beyond
    max_staleness+1 would make ShardedExecutor raise at construction —
    the planner must skip it even when it measured fastest."""
    # n_envs=8, update_interval=32 → learn period 4; publish_interval=2
    # shares gcd 2 with it; 4 shards; max_staleness=0 → min(2,4) > 1
    fast_bad = _fig9_point("async", shards=4, publish_interval=2,
                           n_envs=8, steps=9999.0)
    slow_ok = _fig10_point(4, steps=500.0, n_envs=8)
    pc = planner.plan([fast_bad], [slow_ok], update_interval=32,
                      max_staleness=0)
    assert pc.backend == "sharded"        # not the infeasible 9999 point
    # raising the staleness bound makes the fast point legal again
    pc = planner.plan([fast_bad], [slow_ok], update_interval=32,
                      max_staleness=1)
    assert pc.backend == "async" and pc.publish_interval == 2
    assert pc.max_staleness == 1


def test_plan_lane_split_rides_along():
    actor = {x: 100.0 * x for x in range(1, 9)}
    learner = {x: 300.0 * x ** 0.8 for x in range(1, 9)}
    ref = dse.solve(actor, learner, total=8, update_interval=1.0)
    pc = planner.plan([_fig9_point("fused", steps=800.0, n_envs=8)], [],
                      actor_curve=actor, learner_curve=learner)
    assert (pc.x_actor, pc.x_learner) == (ref.x_actor, ref.x_learner)
    # the executable config keeps the env count the point was MEASURED
    # at — the plan's throughput claim stays on the measured hull
    assert pc.n_envs == 8

    # sharded winner: measured env count, rounded to shard divisibility
    pc = planner.plan([], [_fig10_point(4, steps=9000.0, n_envs=16)],
                      actor_curve=actor, learner_curve=learner)
    assert pc.n_data == 4
    assert pc.n_envs == 16 and pc.n_envs % 4 == 0


def _wallclock_point(shards=2, pods=1, steps=500.0, n_envs=16, ui=1,
                     overlapped=False, compressed=False, n_procs=2):
    return {"backend": "wallclock", "shards": shards, "pods": pods,
            "compressed": compressed, "overlapped": overlapped,
            "n_procs": n_procs, "update_interval": ui, "n_envs": n_envs,
            "env_steps_per_s": steps}


def test_plan_prefers_wallclock_over_emulated_same_config():
    """A config measured both emulated and on a real multi-process gang
    keeps the gang number: emulated host devices time-slice one process,
    so the inflated emulated figure must not win the ranking."""
    emu_2shard = _fig10_point(2, steps=9000.0)     # emulated, inflated
    wc_2shard = _wallclock_point(shards=2, steps=400.0, ui=1)
    emu_4shard = _fig10_point(4, steps=800.0)
    pc = planner.plan([], [emu_2shard, wc_2shard, emu_4shard])
    # the gang's 400 replaces the emulated 9000 for the 2-shard config,
    # so the honestly-slower 4-shard emulated point wins
    assert (pc.backend, pc.n_data) == ("sharded", 4)
    assert pc.predicted_env_steps_per_s == 800.0
    # without the wall-clock measurement the emulated 2-shard wins
    pc = planner.plan([], [emu_2shard, emu_4shard])
    assert (pc.n_data, pc.predicted_env_steps_per_s) == (2, 9000.0)


def test_plan_wallclock_ratio_filter_and_overlap_flows_through():
    """A wall-clock point carries the update_interval it was measured at
    — a different requested ratio is a different workload, so the point
    is filtered; the overlapped-reduce flag flows into the plan (with
    max_staleness pinned to 0: overlap is incompatible with the
    bounded-staleness reduce)."""
    wc = _wallclock_point(shards=1, pods=2, steps=900.0, ui=8,
                          overlapped=True, compressed=True)
    slow = _fig10_point(2, steps=100.0)
    pc = planner.plan([], [wc, slow], update_interval=8, max_staleness=2)
    assert (pc.n_pods, pc.n_data) == (2, 1)
    assert pc.compress_pod_reduce and pc.overlap_pod_reduce
    assert pc.max_staleness == 0
    assert pc.source.endswith("fig10-wallclock")
    # at the default ratio the ui=8 gang point is a different workload
    pc = planner.plan([], [wc, slow], update_interval=1)
    assert (pc.backend, pc.n_data) == ("sharded", 2)
    assert not pc.overlap_pod_reduce


def test_interp_hull_clamps_to_measured_range():
    curve = {2: 200.0, 4: 400.0}
    assert dse.interp_hull(curve, 1) == 200.0     # below the hull → edge
    assert dse.interp_hull(curve, 100) == 400.0   # above the hull → edge
    assert dse.interp_hull(curve, 3) == 300.0     # inside → interpolated
    assert dse.interp_hull(curve, 4) == 400.0


def test_plan_curve_only_fallback_and_empty_inputs():
    actor = {1: 100.0, 2: 200.0}
    learner = {1: 100.0, 2: 200.0}
    pc = planner.plan(actor_curve=actor, learner_curve=learner)
    assert pc.backend == "fused" and pc.n_data == 0
    assert pc.x_actor >= 1
    with pytest.raises(ValueError, match="no feasible"):
        planner.plan()


def test_planned_config_validation():
    with pytest.raises(ValueError, match="backend"):
        planner.PlannedConfig(backend="warp")
    with pytest.raises(ValueError, match="publish_interval"):
        planner.PlannedConfig(backend="async", publish_interval=0)
    with pytest.raises(ValueError, match="synchronous"):
        planner.PlannedConfig(backend="fused", publish_interval=2)
    with pytest.raises(ValueError, match="n_data"):
        planner.PlannedConfig(backend="sharded", n_data=0)
    with pytest.raises(ValueError, match="compress"):
        planner.PlannedConfig(backend="sharded", n_data=2,
                              compress_pod_reduce=True)
    with pytest.raises(ValueError, match="divisible"):
        planner.PlannedConfig(backend="sharded", n_data=4, n_envs=6)
    with pytest.raises(ValueError, match="unknown"):
        planner.PlannedConfig.from_dict({"backend": "fused", "warp": 9})


def test_plan_json_round_trip(tmp_path):
    fig9 = [_fig9_point("fused", steps=1000.0)]
    pc = planner.plan(fig9, [])
    path = tmp_path / "BENCH_plan.json"
    payload = planner.save_plan(pc, str(path),
                                realized_env_steps_per_s=950.0)
    assert payload["realized_env_steps_per_s"] == 950.0
    assert planner.load_plan(str(path)) == pc
    # bare-config dicts work too (hand-written plans)
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(pc.to_dict()))
    assert planner.load_plan(str(bare)) == pc


def test_plan_from_json_dir(tmp_path):
    (tmp_path / planner.FIG9_JSON).write_text(json.dumps(
        {"figure": "fig9", "metric": "env_steps_per_s",
         "points": [_fig9_point("fused", steps=1200.0)]}))
    pc = planner.plan_from_json(str(tmp_path))
    assert pc.backend == "fused"
    assert pc.predicted_env_steps_per_s == 1200.0
    with pytest.raises(FileNotFoundError, match="emit-json"):
        planner.plan_from_json(str(tmp_path / "nope"))


# -- feasibility property test (hypothesis) ----------------------------------


def test_planner_feasibility_property():
    """Whatever the measured points and knobs, a returned plan is always
    instantiable: it matches a measured candidate (config-level profiled
    hull), its lane split respects the budget, envs divide over shards,
    and the async aliasing rule holds (an executor-construction
    ValueError can never come out of a plan)."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=60, deadline=None)
    @hypothesis.given(
        steps=st.lists(st.floats(1.0, 1e6), min_size=1, max_size=6),
        publish=st.lists(st.integers(1, 8), min_size=1, max_size=4),
        shards=st.lists(st.sampled_from([1, 2, 4, 8]), min_size=1,
                        max_size=4),
        update_interval=st.integers(1, 64),
        max_staleness=st.integers(0, 3),
        total=st.integers(2, 12),
    )
    def check(steps, publish, shards, update_interval, max_staleness,
              total):
        fig9 = [_fig9_point("fused", steps=steps[0])]
        fig9 += [_fig9_point("async", shards=s, publish_interval=p,
                             steps=steps[(i + 1) % len(steps)])
                 for i, (p, s) in enumerate(zip(publish, [0] + shards))]
        fig10 = [_fig10_point(s, steps=steps[i % len(steps)])
                 for i, s in enumerate(shards)]
        actor = {x: 50.0 * x for x in (1, 2, 4, 8)}
        learner = {x: 120.0 * x ** 0.7 for x in (1, 2, 4, 8)}
        try:
            pc = planner.plan(fig9, fig10, actor_curve=actor,
                              learner_curve=learner, total_lanes=total,
                              update_interval=update_interval,
                              max_staleness=max_staleness)
        except ValueError as e:
            assert "no feasible" in str(e) or "total=" in str(e)
            return
        # inside the lane budget and the profiled lane hull
        if pc.x_actor:
            assert pc.x_actor + pc.x_learner <= total
            assert 1 <= pc.x_actor <= 8 and 1 <= pc.x_learner <= 8
        # the config itself was measured (candidate hull)
        cands = planner.candidates_from_points(fig9, fig10)
        assert any(c.backend == pc.backend and c.n_pods == pc.n_pods
                   and c.n_data == pc.n_data
                   and c.publish_interval == pc.publish_interval
                   for c in cands)
        # divisibility + aliasing: the executor would accept this
        assert pc.n_envs % pc.n_shards == 0
        period = planner.learn_period(pc.update_interval, pc.n_envs)
        assert planner.aliasing_ok(pc.publish_interval, period,
                                   pc.n_shards, pc.max_staleness)
        if pc.publish_interval and pc.n_shards > 1:
            g = math.gcd(pc.publish_interval, period)
            assert min(g, pc.n_shards) <= pc.max_staleness + 1

    check()


# -- schema + compare gates --------------------------------------------------


def test_schema_accepts_emitted_shapes():
    from benchmarks import schema

    assert schema.validate({"figure": "fig9", "metric": "env_steps_per_s",
                            "smoke": True,
                            "points": [_fig9_point()]}) == "fig9"
    assert schema.validate({"figure": "fig10", "metric": "env_steps_per_s",
                            "points": [_fig10_point(2)]}) == "fig10"
    pc = planner.plan([_fig9_point()], [])
    assert schema.validate({"figure": "plan", "metric": "env_steps_per_s",
                            "config": pc.to_dict(),
                            "predicted_env_steps_per_s": 1.0,
                            "realized_env_steps_per_s": None}) == "plan"


def test_schema_rejects_bad_payloads():
    from benchmarks import schema

    with pytest.raises(schema.SchemaError, match="figure"):
        schema.validate({"figure": "fig99", "points": []})
    with pytest.raises(schema.SchemaError, match="metric"):
        schema.validate({"figure": "fig9", "metric": "bananas",
                         "points": [_fig9_point()]})
    with pytest.raises(schema.SchemaError, match="non-empty"):
        schema.validate({"figure": "fig9", "metric": "env_steps_per_s",
                         "points": []})
    bad = _fig9_point()
    del bad["backend"]
    with pytest.raises(schema.SchemaError, match="backend"):
        schema.validate({"figure": "fig9", "metric": "env_steps_per_s",
                         "points": [bad]})
    bad = _fig9_point()
    bad["env_steps_per_s"] = "fast"
    with pytest.raises(schema.SchemaError, match="env_steps_per_s"):
        schema.validate({"figure": "fig9", "metric": "env_steps_per_s",
                         "points": [bad]})
    bad = _fig10_point(2)
    bad["mystery"] = 1
    with pytest.raises(schema.SchemaError, match="mystery"):
        schema.validate({"figure": "fig10", "metric": "env_steps_per_s",
                         "points": [bad]})


def test_compare_gate(tmp_path):
    from benchmarks import compare

    base_dir = tmp_path / "base"
    fresh_dir = tmp_path / "fresh"
    base_dir.mkdir()
    fresh_dir.mkdir()

    def write(d, fname, points):
        (d / fname).write_text(json.dumps(
            {"figure": "fig9", "metric": "env_steps_per_s",
             "points": points}))

    p_fast = _fig9_point("fused", steps=1000.0)
    p_slow = dict(p_fast, env_steps_per_s=600.0)
    p_jitter = dict(p_fast, env_steps_per_s=820.0)
    p_other = _fig9_point("async", publish_interval=2, steps=500.0)

    # >30% drop on a matching point fails
    write(base_dir, "BENCH_fig9.json", [p_fast])
    write(fresh_dir, "BENCH_fig9.json", [p_slow])
    assert compare.compare_dirs(str(fresh_dir), str(base_dir),
                                compare.THRESHOLD) == 1
    # 18% drop passes the default 30% gate
    write(fresh_dir, "BENCH_fig9.json", [p_jitter])
    assert compare.compare_dirs(str(fresh_dir), str(base_dir),
                                compare.THRESHOLD) == 0
    # missing/new points are tolerated in both directions
    write(base_dir, "BENCH_fig9.json", [p_fast, p_other])
    write(fresh_dir, "BENCH_fig9.json", [p_jitter])
    assert compare.compare_dirs(str(fresh_dir), str(base_dir),
                                compare.THRESHOLD) == 0
    # threshold is read from the one module constant
    assert compare.THRESHOLD == 0.30


def test_compare_fails_hard_when_no_points_match(tmp_path, capsys):
    """An identity-field change (e.g. a new sweep env count) de-matches
    every point: a baseline whose points all fail to match gated
    nothing, so the gate must fail hard, not print a vacuous OK."""
    from benchmarks import compare

    base_dir = tmp_path / "base"
    fresh_dir = tmp_path / "fresh"
    base_dir.mkdir()
    fresh_dir.mkdir()
    old = _fig9_point("fused", n_envs=8, steps=1000.0)
    new = _fig9_point("fused", n_envs=16, steps=100.0)   # huge "drop"
    for d, pt in ((base_dir, old), (fresh_dir, new)):
        (d / "BENCH_fig9.json").write_text(json.dumps(
            {"figure": "fig9", "metric": "env_steps_per_s",
             "points": [pt]}))
    assert compare.compare_dirs(str(fresh_dir), str(base_dir),
                                compare.THRESHOLD) == 1   # blocking
    assert "0 matching points" in capsys.readouterr().out

    # an *empty* baseline points list still gates nothing quietly —
    # only a baseline that has identities to match can fail this way
    (base_dir / "BENCH_fig9.json").write_text(json.dumps(
        {"figure": "fig9", "metric": "env_steps_per_s", "points": []}))
    assert compare.compare_dirs(str(fresh_dir), str(base_dir),
                                compare.THRESHOLD) == 0


# -- plan → executor instantiation -------------------------------------------


def _agent_and_example():
    from repro.agents.dqn import DQNConfig, make_dqn
    from repro.envs.classic import make_vec
    import jax.numpy as jnp

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
    return agent, env_fn, example


def test_executor_from_plan_fused_and_async():
    from repro.runtime.executors import (AsyncExecutor, FusedExecutor,
                                         executor_from_plan)

    agent, env_fn, example = _agent_and_example()
    cfg = LoopConfig(batch_size=32, warmup=0, epsilon=0.3)

    pc = planner.PlannedConfig(backend="fused", n_envs=4, update_interval=4)
    ex = executor_from_plan(pc, agent, env_fn, cfg, example)
    assert isinstance(ex, FusedExecutor)
    assert ex.n_envs == 4
    assert ex.cfg.update_interval == 4    # the plan's ratio wins
    state, hist = ex.train(16, jax.random.PRNGKey(0))
    assert int(hist["env_steps"][-1]) == 64

    pc = planner.PlannedConfig(backend="async", publish_interval=3,
                               max_staleness=0, n_envs=4)
    ex = executor_from_plan(pc, agent, env_fn, cfg, example)
    assert isinstance(ex, AsyncExecutor)
    assert ex.publish_interval == 3


def test_executor_from_plan_sharded_single_device():
    """A 1-shard data mesh exists on any host — the sharded plan path
    end-to-end without forced devices."""
    from repro.runtime.executors import ShardedExecutor, executor_from_plan

    agent, env_fn, example = _agent_and_example()
    cfg = LoopConfig(batch_size=32, warmup=0, epsilon=0.3)
    pc = planner.PlannedConfig(backend="sharded", n_data=1, n_envs=4)
    ex = executor_from_plan(pc, agent, env_fn, cfg, example)
    assert isinstance(ex, ShardedExecutor)
    assert ex.n_shards == 1
    state, hist = ex.train(8, jax.random.PRNGKey(0))
    assert int(hist["env_steps"][-1]) == 32


def test_mesh_from_plan_shapes():
    from repro.launch.mesh import mesh_from_plan

    assert mesh_from_plan(
        planner.PlannedConfig(backend="fused")) is None
    m = mesh_from_plan(planner.PlannedConfig(backend="sharded", n_data=1))
    assert m.axis_names == ("data",) and m.devices.size == 1


@pytest.mark.slow
def test_quickstart_trains_from_plan_json(tmp_path):
    """The acceptance path: a planner-emitted BENCH_plan.json drives
    quickstart into the planned (sharded, forced-device) executor."""
    pc = planner.PlannedConfig(backend="sharded", n_data=2, n_envs=8,
                               update_interval=1,
                               predicted_env_steps_per_s=1234.0,
                               source="test")
    plan_path = tmp_path / "BENCH_plan.json"
    planner.save_plan(pc, str(plan_path))

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)            # quickstart sets the device flag
    # keep the entry point's compile cache out of the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["PYTHONPATH"] = (f"{os.path.join(root, 'src')}:"
                         f"{env.get('PYTHONPATH', '')}").rstrip(":")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "quickstart.py"),
         "--plan", str(plan_path), "--iterations", "48"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "planner-selected sharded executor on 2 device(s)" in r.stdout
    assert "final mean episode return" in r.stdout


# -- replay-service degree of freedom (DESIGN.md §11) ------------------------


def _serve_point(writers=2, n_shards=1, inserts=2000.0, samples=16000.0,
                 spi=8.0, batch=64):
    return {"writers": writers, "n_shards": n_shards, "spi": spi,
            "batch_size": batch, "inserts_per_s": inserts,
            "samples_per_s": samples, "realized_spi": spi,
            "repeats": 3, "rel_spread": 0.01}


def test_select_replay_service_feasibility():
    # spi target = 64/8 = 8 → need 8·insert_rate samples/s
    pts = [_serve_point(n_shards=1, inserts=2000.0, samples=16000.0),
           _serve_point(n_shards=2, inserts=4000.0, samples=32000.0)]
    # both clear 1000 inserts/s and 8000 samples/s — fewest shards win
    assert planner.select_replay_service(
        pts, insert_rate=1000.0, update_interval=8,
        batch_size=64) == (1, 8.0)
    # only the 2-shard config clears 3000 inserts/s
    assert planner.select_replay_service(
        pts, insert_rate=3000.0, update_interval=8,
        batch_size=64) == (2, 8.0)
    # nothing clears 5000 inserts/s → keep the replay in-loop
    assert planner.select_replay_service(
        pts, insert_rate=5000.0, update_interval=8,
        batch_size=64) == (0, 0.0)
    # insert rate fine but sample rate short → in-loop
    assert planner.select_replay_service(
        [_serve_point(inserts=2000.0, samples=100.0)],
        insert_rate=1000.0, update_interval=8, batch_size=64) == (0, 0.0)
    # batch must divide over shards (stratified sampling)
    assert planner.select_replay_service(
        [_serve_point(n_shards=3, inserts=9000.0, samples=72000.0)],
        insert_rate=1000.0, update_interval=8, batch_size=64) == (0, 0.0)
    assert planner.select_replay_service(
        [], insert_rate=1.0, update_interval=1, batch_size=64) == (0, 0.0)


def test_select_replay_service_headroom_tiebreak():
    roomy = _serve_point(writers=1, inserts=8000.0, samples=64000.0)
    tight = _serve_point(writers=4, inserts=1100.0, samples=8800.0)
    for pts in ([roomy, tight], [tight, roomy]):    # order-independent
        shards, spi = planner.select_replay_service(
            pts, insert_rate=1000.0, update_interval=8, batch_size=64)
        assert (shards, spi) == (1, 8.0)


def test_plan_threads_serve_points_into_config():
    fig9 = [_fig9_point("fused", steps=1000.0)]
    serve = [_serve_point(n_shards=2, inserts=4000.0, samples=32000.0)]
    pc = planner.plan(fig9, [], serve_points=serve, update_interval=8,
                      batch_size=64)
    assert pc.n_replay_shards == 2
    assert pc.samples_per_insert == 8.0
    assert "replay service" in pc.describe()
    # round trip keeps the service shape
    assert planner.PlannedConfig(**pc.to_dict()) == pc
    # no serve points → in-loop replay, and describe stays quiet
    pc0 = planner.plan(fig9, [])
    assert (pc0.n_replay_shards, pc0.samples_per_insert) == (0, 0.0)
    assert "replay service" not in pc0.describe()


def test_planned_config_service_validation():
    with pytest.raises(ValueError, match="n_replay_shards"):
        planner.PlannedConfig(backend="fused", n_replay_shards=-1)
    with pytest.raises(ValueError, match="samples_per_insert"):
        planner.PlannedConfig(backend="fused", samples_per_insert=4.0)
    with pytest.raises(ValueError, match="samples_per_insert"):
        planner.PlannedConfig(backend="fused", n_replay_shards=1,
                              samples_per_insert=-1.0)


def test_merge_bench_points_newest_wins(tmp_path):
    old = tmp_path / "old"
    new = tmp_path / "nested" / "new"
    old.mkdir()
    new.mkdir(parents=True)
    stale = _fig9_point("fused", steps=111.0)
    fresh = _fig9_point("fused", steps=999.0)    # same identity, new rate
    other = _fig9_point("async", publish_interval=4, steps=500.0)
    (old / planner.FIG9_JSON).write_text(json.dumps(
        {"figure": "fig9", "metric": "env_steps_per_s",
         "points": [stale, other]}))
    (new / planner.FIG9_JSON).write_text(json.dumps(
        {"figure": "fig9", "metric": "env_steps_per_s",
         "points": [fresh]}))
    os.utime(old / planner.FIG9_JSON, (1_000_000, 1_000_000))
    os.utime(new / planner.FIG9_JSON, (2_000_000, 2_000_000))
    # plan envelopes and junk are skipped, not fatal
    (tmp_path / "BENCH_plan.json").write_text(json.dumps(
        {"figure": "plan", "config": {}}))
    (tmp_path / "BENCH_broken.json").write_text("{not json")

    merged = planner.merge_bench_points(str(tmp_path))
    fig9 = merged["fig9"]
    assert len(fig9) == 2
    by_backend = {p["backend"]: p for p in fig9}
    assert by_backend["fused"]["env_steps_per_s"] == 999.0   # freshest wins
    assert by_backend["async"]["env_steps_per_s"] == 500.0


def test_plan_from_json_merges_serve(tmp_path):
    (tmp_path / planner.FIG9_JSON).write_text(json.dumps(
        {"figure": "fig9", "metric": "env_steps_per_s",
         "points": [_fig9_point("fused", steps=1200.0)]}))
    (tmp_path / planner.SERVE_JSON).write_text(json.dumps(
        {"figure": "serve", "metric": "inserts_per_s",
         "points": [_serve_point(inserts=40000.0, samples=320000.0)]}))
    pc = planner.plan_from_json(str(tmp_path), update_interval=8,
                                batch_size=64)
    assert pc.backend == "fused"
    assert pc.n_replay_shards == 1
    assert pc.samples_per_insert == 8.0


def test_schema_serve_payloads():
    from benchmarks import schema

    good = {"figure": "serve", "metric": "inserts_per_s", "smoke": True,
            "points": [_serve_point()]}
    assert schema.validate(good) == "serve"
    bad = _serve_point()
    del bad["samples_per_s"]
    with pytest.raises(schema.SchemaError, match="samples_per_s"):
        schema.validate({"figure": "serve", "metric": "inserts_per_s",
                         "points": [bad]})
    bad = _serve_point()
    bad["n_shards"] = "two"
    with pytest.raises(schema.SchemaError, match="n_shards"):
        schema.validate({"figure": "serve", "metric": "inserts_per_s",
                         "points": [bad]})


def test_executor_from_plan_replay_service():
    from repro.runtime.executors import executor_from_plan
    from repro.service import ServiceExecutor

    agent, env_fn, example = _agent_and_example()
    cfg = LoopConfig(batch_size=32, warmup=64, epsilon=0.3)
    pc = planner.PlannedConfig(backend="fused", n_envs=4, update_interval=4,
                               n_replay_shards=2, samples_per_insert=8.0)
    ex = executor_from_plan(pc, agent, env_fn, cfg, example)
    assert isinstance(ex, ServiceExecutor)
    assert ex.n_shards == 2
    assert ex.limiter.samples_per_insert == 8.0
    state, hist = ex.train(48, jax.random.PRNGKey(0))
    assert int(hist["env_steps"][-1]) == 192

    # a device mesh and a replay service cannot be combined
    pc = planner.PlannedConfig(backend="sharded", n_data=1, n_envs=4,
                               n_replay_shards=1)
    with pytest.raises(ValueError, match="mesh"):
        executor_from_plan(pc, agent, env_fn, cfg, example)
