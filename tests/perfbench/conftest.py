import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny(cell, capacity=2 ** 14):
    """The cell at a size a CPU test run holds: the traffic's ratio kept,
    the buffer and the chunk cut, then the family's own cuts."""
    from perfbench import algorithms

    traffic = dict(cell.traffic, fill_envs=1024, scan_chunk=1)
    if traffic.get("mesh"):
        traffic.update(n_envs=64, batch_size=256)
    else:
        traffic.update(batch_size=64)
    config = dict(cell.config, replay_capacity=capacity)
    config, traffic = algorithms.of(config).tiny(config, traffic)
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.fixture
def tiny_cell():
    from perfbench.spec import resolve

    return lambda workload: tiny(resolve(workload))
