"""Record a small slice of a cell's profiler trace on the chip, for
``test_perfbench_trace.py``.

    python3 tests/perfbench/record_fixture.py --workload <cell> \
        --seed <n> --out <file.json> [--raw <dir>] [--span-us 3000]

Runs the cell's probe, then traces one chunk and keeps, of every TPU
plane's ``XLA Ops`` line and of the host planes, the events that
overlap ``--span-us`` microseconds from the first replay kernel or
collective (else from the first op), as the plain tuples that
``trace_reduce.reduce_planes`` reads.  Names are kept as the trace
gives them; string stats are cut to 400 characters.  ``--raw`` keeps
the whole ``.xplane.pb`` as well.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _stats(stats):
    return {k: (v[:400] if isinstance(v, str) else v)
            for k, v in stats.items() if isinstance(v, (str, int, float))}


def slice_planes(planes, span_ns):
    from perfbench import trace_reduce as tr

    ops = [ev for name, lines in planes if name.startswith(tr.DEVICE_PREFIX)
           for lname, evs in lines if lname == tr.OPS_LINE for ev in evs]
    if not ops:
        raise SystemExit("record_fixture: no device op in the trace")
    marked = [ev for ev in ops if tr.kernel_of(ev)
              or tr.collective_of(ev)]
    t0 = min(ev[1] for ev in (marked or ops)) - span_ns / 4
    t1 = t0 + span_ns
    out = []
    for name, lines in planes:
        if not (name.startswith(tr.DEVICE_PREFIX)
                or name.startswith(tr.HOST_PREFIX)):
            continue
        kept = []
        for lname, evs in lines:
            if name.startswith(tr.DEVICE_PREFIX) and lname != tr.OPS_LINE:
                continue
            sel = [[n, s - t0, d, _stats(st)] for n, s, d, st in evs
                   if s < t1 and s + d > t0]
            if sel:
                kept.append([lname, sel])
        if kept:
            out.append([name, kept])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--raw")
    ap.add_argument("--span-us", type=float, default=3000.0)
    args = ap.parse_args(argv)

    import jax

    from perfbench import cell as cell_mod
    from perfbench import trace_reduce as tr
    from perfbench.run import require_tpu
    from perfbench.spec import resolve
    from repro import compile_cache

    cell = resolve(args.workload, ROOT)
    require_tpu(cell.chips)
    compile_cache.enable()
    p = cell_mod.probe(cell, args.seed)
    state, p.state = p.state, None
    tdir = tempfile.mkdtemp(prefix="perfbench_fixture_")
    jax.profiler.start_trace(tdir)
    try:
        cell_mod._run_chunks(p.built.executor, state, 1)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = tr.find_xplane(tdir)
    planes = tr.planes_of(ProfileData.from_file(path))
    if args.raw:
        os.makedirs(args.raw, exist_ok=True)
        shutil.copy(path, args.raw)
    shutil.rmtree(tdir, ignore_errors=True)
    fixture = slice_planes(planes, args.span_us * 1e3)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "device_kind":
                   p.devices[0].device_kind, "planes": fixture}, f)
    red = tr.reduce_planes([(n, [(ln, [tuple(e) for e in evs])
                                 for ln, evs in lines])
                            for n, lines in fixture])
    for d in red.devices:
        print(f"{d.name}: busy {d.busy_ns:.0f} ns, kernels {d.kernel_ns}, "
              f"calls {d.kernel_calls}, collectives {d.collective_ns}")


if __name__ == "__main__":
    main()
