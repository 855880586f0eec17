"""Readings that the limits in ``perfbench/limits/`` are set from.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--faults unchanged,half_batch] \
        [--fault-seeds 7,8,9]

In one process, at the cell's own size and on the chip: the program's
readings on each seed (the lower readings), the control's (the
reference computed in bfloat16 on the chip, put in the program's
place) and each fault's, planted under the timed path (the upper
readings).  One JSON line per reading on standard output.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from perfbench import cell as cell_mod
    from perfbench import check, faults
    from perfbench.run import require_tpu
    from perfbench.spec import resolve
    from repro import compile_cache

    cell = resolve(args.workload, ROOT)
    require_tpu(cell.chips)
    compile_cache.enable()

    def emit(kind, seed, values):
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          **values}), flush=True)

    def program(seeds, kind, b=None):
        b = b or cell_mod.build(cell)
        for seed in seeds:
            p = cell_mod.probe(cell, seed, b)
            p.state = None
            rows = cell_mod.host_rows(b, p.rows)
            p.rows = None
            gc.collect()
            expected = cell_mod.expected_counters(cell, b)
            ref = cell_mod.follow(cell, b, seed, rows)
            emit(kind, seed, check.readings(p.probe, ref, expected))
            if kind == "program" and seed in control_seeds:
                ctrl = cell_mod.follow(cell, b, seed, rows,
                                       dtype=jnp.bfloat16,
                                       device=jax.devices()[0])
                emit("control", seed, check.readings(
                    check.probe_of(ctrl, b.capacity, expected), ref,
                    expected))
        return b

    control_seeds = set(_seeds(args.control_seeds))
    seeds = _seeds(args.seeds)
    seeds += [s for s in sorted(control_seeds) if s not in seeds]
    if seeds:
        program(seeds, "program")
    for name in [f for f in args.faults.split(",") if f]:
        gc.collect()
        with faults.FAULTS[name]():
            program(_seeds(args.fault_seeds), "fault:" + name)


if __name__ == "__main__":
    main()
