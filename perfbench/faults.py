"""Faults planted under the timed path, for the tests that show the
comparison catches them.

Each is a context manager that breaks the program at run time; nothing
is changed on disk.  Plant it before the cell is built: the step is
traced at its first call, and the patched names are read then.

* ``unchanged``: the optimizer step returns the parameters and its
  state unchanged.
* ``half_batch``: the learner gets only the first half of each sampled
  batch: its loss is the mean over that half, and only that half's
  priorities are written back.
* ``no_exchange``: the gradients are not averaged across chips.
* ``wrong_leaf``: the priority write-back lands one leaf off the draw.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def unchanged():
    from repro.optim import adam

    orig = adam.update

    def update(grads, state, params, cfg):
        _, _, gnorm = orig(grads, state, params, cfg)
        return params, state, gnorm
    with _patched(adam, "update", update):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.core.replay import PrioritizedReplay

    orig = PrioritizedReplay.sample

    def sample(self, state, rng, batch, *args, **kwargs):
        idx, items, w = orig(self, state, rng, batch, *args, **kwargs)
        half = batch // 2
        return idx[:half], jax.tree.map(lambda x: x[:half], items), w[:half]
    with _patched(PrioritizedReplay, "sample", sample):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.runtime import learner

    with _patched(learner, "pmean_gradients",
                  lambda grads, axes, dtype=None: grads):
        yield


@contextlib.contextmanager
def wrong_leaf():
    from repro.core.replay import PrioritizedReplay

    orig = PrioritizedReplay.update_priorities

    def update_priorities(self, state, idx, td, *, lazy=False):
        shifted = (idx + 1) % self.config.capacity
        return orig(self, state, shifted, td, lazy=lazy)
    with _patched(PrioritizedReplay, "update_priorities", update_priorities):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "wrong_leaf": wrong_leaf}


def faults_for(cell) -> list:
    """The faults a cell can have: the exchange exists only on a mesh."""
    names = ["unchanged", "half_batch", "wrong_leaf"]
    if cell.traffic.get("mesh"):
        names.append("no_exchange")
    return names
