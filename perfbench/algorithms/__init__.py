"""Algorithm families, one module each, found by a configuration's
``"algorithm"`` value as ``perfbench/algorithms/<algorithm>.py``.

The shared harness (``cell.py``, ``reference.py``, ``work.py``) names no
family; a later change adds one as a new module that holds:

* ``build(config, traffic, opt) -> (agent, env_fn, example)``: the
  program's agent bundle with the Adam settings ``opt``, the vectorized
  env maker ``env_fn(n) -> (spec, v_reset, v_step)`` the executor takes,
  and one example row of the replay; raises ``ValueError`` where the env
  does not match the configuration.  The agent is the program's own: it
  never calls the module's ``init_params`` or ``loss``, which are the
  reference's.
* ``fill(config, n) -> (start, step)``: ``start(key) -> carry`` and
  ``step(carry, key) -> (carry, row)``, ``n`` rows of a uniformly random
  policy in the example row's layout and dtypes (the harness scans it,
  appends each row through the replay's own writer and flushes once).
* ``init_params(config, key)``: the agent's weights from the agent key of
  the loop's initial state, as the program makes them.
* ``loss(config) -> f(params, target, batch, weights)``, returning
  ``(loss, (td, scale))``: the loss the learner differentiates, its TD
  errors and the magnitude ``loss_gap`` is relative to.  Written with
  plain ``jnp``, it imports nothing of the program.  Rows reach it with
  floating leaves in the reference's dtype and integer leaves as stored.
* ``act_flops_per_row(config)``, ``learn_flops_per_row(config)``,
  ``row_bytes(config)``: the work one action, one sampled row through
  one update, and one stored row require (``work.py``).
* ``tiny(config, traffic) -> (config, traffic)``: the family's own cuts
  (depth, widths) for a CPU test, after the harness has cut the buffer,
  batch and chunk.
* ``check_config(config)``: raises ``ValueError`` where a configuration
  file of the family departs from what its cells are sized to.

Modules whose names start with ``_`` are helpers, not families.
"""

from __future__ import annotations

import importlib
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))


def names() -> List[str]:
    """The families present."""
    return sorted(f[:-3] for f in os.listdir(HERE)
                  if f.endswith(".py") and not f.startswith("_"))


def load(name: str):
    """The module of the family ``name``."""
    have = names()
    if name not in have:
        raise KeyError(f"no algorithm family {name!r} in perfbench/algorithms "
                       f"(have {have})")
    return importlib.import_module(f"{__name__}.{name}")


def of(config: dict):
    """The module of the configuration's family."""
    return load(config["algorithm"])
