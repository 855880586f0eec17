"""The comparison that decides ``correct``.

Each number is compared with its limit from ``limits/<workload>.json``;
a run is correct when every number is at or under its limit.

* ``loss_gap``: the first iteration's loss as the program reports it
  (the mean over that iteration's learner updates, and over shards)
  against the reference's, relative to the reference's loss; for DDPG,
  whose loss is critic + actor and can come near 0, relative to
  |critic| + |actor|.  It covers the draws, the gathered
  rows, the importance weights, the loss, Adam and the target net (each
  update after the first is scored at the parameters the ones before it
  made), the write-back and flush between updates, and on several chips
  the gradient reduce.
* ``prio_gap``: at the leaves the reference draws in the first update,
  the priority the program's tree holds after the probe chunk against
  the one the reference writes there, relative; the upper quartile
  over the leaves.  That draw is exact: every leaf then holds the
  integer priority 1, so the prefix sums are exact in f32.  So it
  covers the inverse-CDF descent, the gather, the TD error (target
  net, double-Q selection or target policy) and the priority
  write-back and flush.  Later draws are not compared: once the first
  write-back has made the leaves fractional, a draw whose residual
  falls on a leaf boundary can go either way, and one such draw moves
  the total by the difference of two priorities, which for DDPG is
  about one leaf's width and shifts most of the next update's draws
  by a leaf.  The upper quartile passes over the leaves that a later
  draw or insert of the chunk overwrote, while they are fewer than a
  quarter, and fails a write-back that misses half of the batch.
* ``counters``: env steps, learner updates, optimizer steps and stored
  rows after the probe against the schedule; exact.  The optimizer's
  own step count catches a step that returns its state unchanged.
* ``param_spread``: the largest difference between the copies of the
  replicated parameters on the chips; exact.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def first_update_gaps(probe: dict, followed) -> np.ndarray:
    """The relative gap of the program's leaf priority at each leaf the
    reference drew in its first update, against what it wrote there."""
    gaps = []
    for d, per_learn in enumerate(followed.per_learn):
        idx, val = per_learn[0]
        ref = dict(zip(idx.tolist(), val.tolist()))    # last writer wins
        prog = probe["leaves"][d][np.fromiter(ref, np.int64)]
        want = np.fromiter(ref.values(), np.float64)
        gaps.append(np.abs(prog.astype(np.float64) - want)
                    / np.maximum(want, 1e-30))
    return np.concatenate(gaps)


def readings(probe: dict, followed, expected: dict) -> Dict[str, float]:
    loss_gap = (abs(probe["loss0"] - followed.loss)
                / max(abs(followed.loss_scale), 1e-30))
    counters = sum(abs(probe[k] - v) for k, v in expected.items())
    return {"loss_gap": float(loss_gap),
            "prio_gap": float(np.quantile(first_update_gaps(probe, followed),
                                          0.75)),
            "counters": float(counters),
            "param_spread": float(probe["param_spread"])}


def compare(probe: dict, followed, expected: dict, limits: dict
            ) -> Dict[str, dict]:
    """Every reading that has a limit, beside its limit."""
    values = readings(probe, followed, expected)
    return {name: {"value": values[name], "limit": float(limit)}
            for name, limit in limits.items()}


def all_within(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def probe_of(followed, capacity: int, expected: dict) -> dict:
    """A probe as if ``followed`` (the control) had run in the program's
    place: its loss, and the priorities it left at the leaves it drew."""
    leaves = []
    for per_learn in followed.per_learn:
        lv = np.ones((capacity,), np.float32)
        for idx, pri in per_learn:
            lv[idx] = pri
        leaves.append(lv)
    return dict(expected, loss0=followed.loss, leaves=leaves,
                param_spread=0.0)


def report_lines(checks: Dict[str, dict]):
    return [f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}"
            for name, c in checks.items()]
