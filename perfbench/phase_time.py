"""Device time by phase of the step, for the per-layer metrics that
read it.

The program names the phases of its step with ``jax.named_scope``
(``repro/runtime/phases.py``) and ``Executor.op_phases(state)`` maps
the compiled chunk's HLO instructions to them.  A TPU profile names
each op by its instruction, so a phase's time is the self time
(``DeviceStats.op_ns``) of its ops; ops of no phase (the chunk's
``while``, the per-chunk metric reduce, programs other than the chunk)
are "unattributed".  The metrics read a phase's share of the busy self
time (phases and unattributed together); each phase's device time per
loop iteration, the unattributed time with the ops that hold most of
it, and their sum are logged on standard error beside the metrics.

The map is ``ctx["op_phases"]`` where the runner put one there; else
the cell's executor is built again from ``ctx``'s configuration and
traffic and its chunk compiled for the state's shapes: the program the
window ran, compiled a second time (read from the persistent compile
cache where set-up wrote it), so its instructions carry the same
names.  A program that names no phases gives no map, and every reader
here then returns None.
"""

from __future__ import annotations

import sys
import types
from typing import Dict, Iterable, Optional


def op_phases(ctx) -> Optional[Dict[str, str]]:
    if "op_phases" not in ctx:
        ctx["op_phases"] = _compiled_op_phases(ctx)
    return ctx["op_phases"]


def _compiled_op_phases(ctx) -> Optional[Dict[str, str]]:
    import jax
    import jax.numpy as jnp

    from repro.runtime import executors

    if not hasattr(executors.Executor, "op_phases"):
        return None
    from perfbench import cell

    ex = cell.build(types.SimpleNamespace(config=ctx["config"],
                                          traffic=ctx["traffic"])).executor
    init = jax.jit(ex.init)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    shapes = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh,
                                           weak_type=s.weak_type),
        jax.eval_shape(init, key), init.lower(key).compile().output_shardings)
    return ex.op_phases(shapes)


def phase_ns(ctx) -> Optional[Dict[Optional[str], float]]:
    """Device self time per phase in the traced window, ns, the mean
    over chips; key None holds the unattributed ops.  None without a
    map or a device plane."""
    if "phase_ns" in ctx:
        return ctx["phase_ns"]
    devices = ctx["reduced"].devices
    phases = op_phases(ctx) if devices else None
    out = None
    if phases:
        out, loose = {}, {}
        for d in devices:
            for op, ns in d.op_ns.items():
                phase = phases.get(op)
                out[phase] = out.get(phase, 0.0) + ns / len(devices)
                if phase is None:
                    loose[op] = loose.get(op, 0.0) + ns / len(devices)
        _log(ctx, out, loose)
    ctx["phase_ns"] = out
    return out


def share(ctx, names: Iterable[str]) -> Optional[float]:
    """Device self time of ``names`` over the busy self time (phases and
    unattributed), %; None where none of them ran."""
    times = phase_ns(ctx)
    if not times:
        return None
    ns = sum(times.get(p, 0.0) for p in names)
    if ns <= 0:
        return None
    return 100.0 * ns / sum(times.values())


def _log(ctx, times, loose, top=5):
    iters = ctx["iterations"] or 1
    us = lambda ns: f"{ns / iters / 1e3:.3f}"
    busy = sum(times.values())
    parts = [f"{p or 'unattributed'} {us(ns)}"
             for p, ns in sorted(times.items(), key=lambda kv: -kv[1])]
    loose_pct = 100.0 * times.get(None, 0.0) / busy if busy > 0 else 0.0
    ops = sorted(loose.items(), key=lambda kv: -kv[1])[:top]
    print(f"phases (device self time, us per iteration, mean over chips): "
          f"{', '.join(parts)}; busy self time {us(busy)}; unattributed "
          f"{loose_pct:.3f}%, most in "
          f"{', '.join(f'{op} {us(ns)}' for op, ns in ops) or 'no op'}",
          file=sys.stderr, flush=True)
