"""Perf-regression gate: diff fresh BENCH json against the committed
repo-root baselines.

    PYTHONPATH=src python -m benchmarks.compare out/ [--baseline-dir .]

Points are matched on their identity fields (backend, shard/pod counts,
async knobs — everything except the measured throughput); a fresh point
slower than its baseline by more than its tolerance fails the gate
(exit 1).  The tolerance is per point: ``THRESHOLD`` plus the larger
recorded ``rel_spread`` of the two measurements — a point whose
median-of-N repeats disperse widely (noisy multi-process gang points,
cold CI runners) gets exactly that much extra slack, while tight
points keep the tight gate.  Missing points on either side are
tolerated with a note — sweeps grow and shrink across PRs, and a
baseline measured on different hardware only gates *relative*
regressions on matching points — but a baseline file whose points
*all* fail to match (an identity-field rename de-matching the whole
sweep) is a hard failure: a gate that matched nothing checked
nothing.  CI runs this as a **blocking** step
(the bench-smoke job fails on regression).

THRESHOLD is the one place the base tolerance lives — CI, the cron
sweep and local runs all read it from here (override per-run with
--threshold).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, Iterable, List, Tuple

# >30% env-steps/s regression on a matching point fails the gate.
# Generous on purpose: CI runners are noisy; this catches structural
# slowdowns (a backend falling off a cliff), not jitter.
THRESHOLD = 0.30

BENCH_FILES = ("BENCH_fig9.json", "BENCH_fig10.json", "BENCH_replay.json",
               "BENCH_serve.json", "BENCH_actor.json")

# fields that identify a point (everything but the measurements); the
# median-of-N dispersion record (repeats/rel_spread) is measurement-side
# so old baselines without it still match.  samples_per_s and
# realized_spi are the serve figure's secondary measurements, and the
# actor figure's latencies/swap counts are likewise secondary — each
# gate compares its figure's primary metric only.  ``platform`` labels
# where a point ran; every committed baseline is a CPU point, so it does
# not split identities (chip numbers live in the perf ledger, not here).
_MEASUREMENT_FIELDS = {"env_steps_per_s", "replay_ops_per_s",
                       "inserts_per_s", "speedup_vs_sync",
                       "repeats", "rel_spread", "platform",
                       "samples_per_s", "realized_spi", "recovery_s",
                       "requests_per_s", "p50_ms", "p99_ms",
                       "p99_before_swap_ms", "p99_after_swap_ms",
                       "param_swaps"}


def point_key(point: dict) -> Tuple:
    """Identity of a measured point: every non-measurement field,
    sorted — robust to schema growth (a new identity knob simply makes
    old points unmatched, which is tolerated)."""
    return tuple(sorted(
        (k, v) for k, v in point.items() if k not in _MEASUREMENT_FIELDS))


def _load_points(path: str) -> Tuple[Dict[Tuple, Tuple[float, float]], str]:
    """key → (measured rate, recorded rel_spread) per point; points
    without a dispersion record get spread 0 (no extra slack)."""
    with open(path) as f:
        payload = json.load(f)
    # each payload names its own measured rate (schema.FIGURE_METRICS)
    metric = payload.get("metric", "env_steps_per_s")
    return ({point_key(p): (float(p[metric]),
                            float(p.get("rel_spread", 0.0)))
             for p in payload.get("points", ())}, metric)


def compare_points(baseline: Dict[Tuple, Tuple[float, float]],
                   fresh: Dict[Tuple, Tuple[float, float]],
                   threshold: float, metric: str = "env_steps_per_s"
                   ) -> Tuple[List[str], List[str]]:
    """Returns (regressions, notes) — regressions non-empty fails the
    gate.  Each matched point fails below ``threshold + max(baseline
    rel_spread, fresh rel_spread)``: the recorded median-of-N dispersion
    widens that point's tolerance, so a noisy measurement can't trip the
    gate on jitter its own repeats already exhibited."""
    regressions, notes = [], []
    for key, (base_v, base_rs) in sorted(baseline.items()):
        label = ", ".join(f"{k}={v}" for k, v in key)
        if key not in fresh:
            notes.append(f"baseline-only point (skipped): {label}")
            continue
        fresh_v, fresh_rs = fresh[key]
        delta = (fresh_v - base_v) / base_v
        tol = threshold + max(base_rs, fresh_rs)
        line = (f"{label}: {base_v:,.0f} → {fresh_v:,.0f} {metric} "
                f"({delta:+.1%}, tol -{tol:.0%})")
        if delta < -tol:
            regressions.append(line)
        else:
            notes.append(line)
    for key in sorted(set(fresh) - set(baseline)):
        label = ", ".join(f"{k}={v}" for k, v in key)
        notes.append(f"new point (no baseline): {label}")
    return regressions, notes


def compare_dirs(fresh_dir: str, baseline_dir: str, threshold: float,
                 files: Iterable[str] = BENCH_FILES) -> int:
    """Diff every BENCH file present in both dirs; returns the number of
    regressed points (0 = gate passes)."""
    total_regressions = 0
    compared_any = False
    for name in files:
        fresh_path = os.path.join(fresh_dir, name)
        base_path = os.path.join(baseline_dir, name)
        if not os.path.exists(fresh_path):
            print(f"-- {name}: no fresh measurement (skipped)")
            continue
        if not os.path.exists(base_path):
            print(f"-- {name}: no committed baseline (skipped)")
            continue
        compared_any = True
        baseline_pts, metric = _load_points(base_path)
        fresh_pts, _ = _load_points(fresh_path)
        regressions, notes = compare_points(baseline_pts, fresh_pts,
                                            threshold, metric)
        print(f"-- {name} (fail below -{threshold:.0%}):")
        for line in notes:
            print(f"   {line}")
        for line in regressions:
            print(f"   REGRESSION {line}")
        matched = len(set(baseline_pts) & set(fresh_pts))
        if baseline_pts and not matched:
            # an identity-field change (e.g. a new sweep env count) can
            # de-match every point at once, which would make the gate
            # vacuously green exactly when it matters most — a committed
            # baseline with zero matching fresh points is a hard failure,
            # not a note
            print(f"   FAIL: 0 matching points between baseline and "
                  f"fresh {name} — the gate checked nothing; "
                  "re-commit baselines from a fresh --emit-json run")
            total_regressions += 1
        total_regressions += len(regressions)
    if not compared_any:
        print("no BENCH file present on both sides — nothing gated")
    return total_regressions


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("fresh_dir",
                    help="directory with freshly emitted BENCH json "
                         "(benchmarks/run.py --emit-json)")
    ap.add_argument("--baseline-dir", default=".",
                    help="directory with the committed baselines "
                         "(default: repo root)")
    ap.add_argument("--threshold", type=float, default=THRESHOLD,
                    help="relative env-steps/s drop that fails "
                         f"(default {THRESHOLD})")
    args = ap.parse_args()
    n = compare_dirs(args.fresh_dir, args.baseline_dir, args.threshold)
    if n:
        print(f"FAIL: {n} regressed point(s) beyond "
              f"-{args.threshold:.0%}", file=sys.stderr)
        return 1
    print("perf gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
