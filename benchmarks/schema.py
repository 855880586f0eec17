"""Schema for the machine-readable BENCH json (the perf trajectory CI
gates on).

One place defines what ``benchmarks/run.py --emit-json`` may write and
what ``benchmarks/compare.py`` and ``runtime/planner.py`` may assume:
every payload carries ``figure``/``metric`` (``FIGURE_METRICS`` names
the one measured rate per figure), and the executor sweeps (fig9/fig10)
share the ``env_steps_per_s`` unit — the invariant that makes
cross-file candidate scoring in the planner legal.  The replay
microbenchmark payload carries its own unit (``replay_ops_per_s``) and
is never scored against the executor sweeps.  Points may carry the
median-of-N dispersion record (``repeats``/``rel_spread``,
benchmarks/timing.py).

Dependency-free on purpose (no jsonschema): CI validates the artifacts
with the same stdlib-only code the planner imports.

    PYTHONPATH=src python -m benchmarks.schema out/BENCH_fig9.json ...

exits non-zero on the first invalid file.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List

# field name → (type(s), required) per point, keyed by payload "figure".
# bool is checked before int (bool is an int subclass in Python).
# Every point may carry the median-of-N dispersion record
# (benchmarks/timing.py): repeats + rel_spread.
_COMMON_POINT = {
    "n_envs": (int, False),
    "repeats": (int, False),
    "rel_spread": ((int, float), False),
}

# the one measured rate per figure — compare.py reads the payload's
# "metric" to find it, so every figure's unit stays self-describing
FIGURE_METRICS: Dict[str, str] = {
    "fig9": "env_steps_per_s",
    "fig10": "env_steps_per_s",
    "replay": "replay_ops_per_s",
    "serve": "inserts_per_s",
    "actor": "requests_per_s",
}

POINT_FIELDS: Dict[str, Dict[str, tuple]] = {
    "fig9": {
        **_COMMON_POINT,
        "env_steps_per_s": ((int, float), True),
        "backend": (str, True),
        "shards": (int, True),
        "pods": (int, True),
        "publish_interval": (int, True),
        "max_staleness": (int, True),
        "speedup_vs_sync": ((int, float), False),
    },
    "fig10": {
        **_COMMON_POINT,
        "env_steps_per_s": ((int, float), True),
        "backend": (str, True),
        "shards": (int, True),
        "pods": (int, True),
        "compressed": (bool, True),
        # wall-clock arm (backend="wallclock"): real multi-process gang
        # points from launch/multiprocess.py — the process count and the
        # reduce shape are identity fields, so a wall-clock point never
        # silently matches an emulated one in compare.py
        "n_procs": (int, False),
        "overlapped": (bool, False),
        "update_interval": (int, False),
        # the JAX platform of a point measured in a child process pinned
        # to the host (forced host devices, gloo gangs): "cpu"
        "platform": (str, False),
    },
    # replay-service throughput (benchmarks/fig_serve.py): sustained
    # insert and sample rates of the sharded rate-limited ReplayService
    # vs concurrent writer count — the planner's service-shape inputs
    # (runtime/planner.py select_replay_service).  realized_spi is
    # measurement-side (compare.py ignores it for identity).
    "serve": {
        **_COMMON_POINT,
        "inserts_per_s": ((int, float), True),
        "samples_per_s": ((int, float), True),
        "writers": (int, True),
        "n_shards": (int, True),
        "spi": ((int, float), True),       # configured samples-per-insert
        "batch_size": (int, True),
        "realized_spi": ((int, float), False),
        # recovery arm (fig_serve --fault, DESIGN.md §14): the server is
        # crashed mid-run and restored from shard snapshots.  fault and
        # outage_s (the deliberate downtime) are identity fields;
        # recovery_s (kill → first re-admitted append ack) is the arm's
        # measured quantity alongside the rate metrics.
        "fault": (bool, False),
        "outage_s": ((int, float), False),
        "recovery_s": ((int, float), False),
    },
    # actor-serve load generator (benchmarks/fig_actor.py): sustained
    # request rate + client latency of the continuous-batching inference
    # frontend (repro/serve) under N simulated users, with the mid-run
    # param-publication drill's p99 split.  Latencies and swap counts
    # are measurement-side (compare.py gates requests_per_s only).
    "actor": {
        **_COMMON_POINT,
        "requests_per_s": ((int, float), True),
        "users": (int, True),
        "target_rps": ((int, float), True),
        "overload": (bool, True),
        "slots": (int, True),
        "gen_tokens": (int, True),
        "arch": (str, True),
        "prompt_buckets": (str, True),
        "p50_ms": ((int, float), True),
        "p99_ms": ((int, float), True),
        "p99_before_swap_ms": ((int, float), False),
        "p99_after_swap_ms": ((int, float), False),
        "param_swaps": (int, False),
    },
    # replay-transaction microbenchmark (benchmarks/replay_micro.py)
    "replay": {
        **_COMMON_POINT,
        "replay_ops_per_s": ((int, float), True),
        "backend": (str, True),
        "mode": (str, True),        # "eager" | "lazy"
        "fused": (bool, True),      # fused sample+gather kernel arm
        "capacity": (int, True),
        "fanout": (int, True),
        "insert_batch": (int, True),
        "sample_batch": (int, True),
    },
}

PLAN_CONFIG_FIELDS: Dict[str, tuple] = {
    "backend": (str, True),
    "n_pods": (int, True),
    "n_data": (int, True),
    "publish_interval": (int, True),
    "max_staleness": (int, True),
    "compress_pod_reduce": (bool, True),
    # optional so hand-written pre-overlap plans stay loadable; every
    # planner-emitted plan carries it (PlannedConfig.to_dict)
    "overlap_pod_reduce": (bool, False),
    "n_envs": (int, True),
    "update_interval": (int, True),
    "x_actor": (int, True),
    "x_learner": (int, True),
    # replay-service degrees of freedom (DESIGN.md §11) — optional so
    # pre-service plans stay loadable; planner-emitted plans carry both
    "n_replay_shards": (int, False),
    "samples_per_insert": ((int, float), False),
    "predicted_env_steps_per_s": ((int, float), True),
    "source": (str, True),
}

METRIC = "env_steps_per_s"


class SchemaError(ValueError):
    """A BENCH payload that CI must not gate on."""


def _check_fields(obj: Dict[str, Any], fields: Dict[str, tuple],
                  where: str) -> None:
    if not isinstance(obj, dict):
        raise SchemaError(f"{where}: expected an object, got {type(obj).__name__}")
    for name, (types, required) in fields.items():
        if name not in obj:
            if required:
                raise SchemaError(f"{where}: missing required field {name!r}")
            continue
        val = obj[name]
        # bools pass isinstance(..., int); only admit them where declared
        if isinstance(val, bool) and types is not bool and bool not in (
                types if isinstance(types, tuple) else (types,)):
            raise SchemaError(
                f"{where}.{name}: expected {types}, got bool")
        if not isinstance(val, types):
            raise SchemaError(
                f"{where}.{name}: expected {types}, got {type(val).__name__} "
                f"({val!r})")
    unknown = set(obj) - set(fields)
    if unknown:
        raise SchemaError(f"{where}: unknown fields {sorted(unknown)}")


def validate(payload: Dict[str, Any]) -> str:
    """Validate one BENCH payload; returns its figure name.  Raises
    ``SchemaError`` with the offending path in the message."""
    if not isinstance(payload, dict):
        raise SchemaError(f"payload is {type(payload).__name__}, not an object")
    figure = payload.get("figure")
    if figure in POINT_FIELDS:
        metric = FIGURE_METRICS[figure]
        if payload.get("metric") != metric:
            raise SchemaError(f"{figure}: metric must be {metric!r}, got "
                              f"{payload.get('metric')!r}")
        points = payload.get("points")
        if not isinstance(points, list) or not points:
            raise SchemaError(f"{figure}: 'points' must be a non-empty list")
        for i, p in enumerate(points):
            _check_fields(p, POINT_FIELDS[figure], f"{figure}.points[{i}]")
            if p[metric] <= 0:
                raise SchemaError(
                    f"{figure}.points[{i}].{metric} must be > 0")
        return figure
    if figure == "plan":
        if payload.get("metric") != METRIC:
            raise SchemaError(f"plan: metric must be {METRIC!r}, got "
                              f"{payload.get('metric')!r}")
        _check_fields(payload.get("config"), PLAN_CONFIG_FIELDS, "plan.config")
        realized = payload.get("realized_env_steps_per_s")
        if realized is not None and not isinstance(realized, (int, float)):
            raise SchemaError("plan.realized_env_steps_per_s must be a "
                              "number or null")
        return figure
    raise SchemaError(f"unknown figure {figure!r} — expected one of "
                      f"{sorted(POINT_FIELDS) + ['plan']}")


def validate_file(path: str) -> str:
    with open(path) as f:
        try:
            payload = json.load(f)
        except json.JSONDecodeError as e:
            raise SchemaError(f"{path}: not valid json ({e})") from e
    try:
        return validate(payload)
    except SchemaError as e:
        raise SchemaError(f"{path}: {e}") from e


def main(argv: List[str]) -> int:
    if not argv:
        print("usage: python -m benchmarks.schema BENCH_*.json ...",
              file=sys.stderr)
        return 2
    for path in argv:
        figure = validate_file(path)
        print(f"OK {path} ({figure})")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv[1:]))
    except SchemaError as e:
        print(f"SCHEMA ERROR: {e}", file=sys.stderr)
        sys.exit(1)
