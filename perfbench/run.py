"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics over ``--seconds``;
``--trace 1`` records a short profiled window and reports its per-layer
metrics.  Both compare the probe chunk with the plain reference and
print each number compared beside its limit, last on standard error
and as the last key of the result.  The result is the last line of
standard output.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def allow_queue(depth: int):
    """Let the TPU runtime hold ``depth`` programs per device before the
    host's next dispatch blocks; its own limit is 32.  The queue a cell
    keeps (traffic ``chunks_in_flight``, ``trace_chunks``) is data, and
    32 chunks of the XLA cells' chunks are under a second of work, too
    little to ride out a host stall.  Set before JAX makes its
    backends."""
    import jax

    jax.config.update("jax_pjrt_client_create_options",
                      {"max_inflight_computations": depth})


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"perfbench: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); the benchmark runs "
                         "only on a TPU")
    if len(devices) < n_chips:
        raise SystemExit(f"perfbench: the cell needs {n_chips} TPU chips, "
                         f"found {len(devices)}")
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "perfbench_tpu_logs"))

    from perfbench import cell as cell_mod
    from perfbench import check
    from perfbench.spec import resolve

    cell = resolve(args.workload, ROOT)
    allow_queue(max(cell.traffic["chunks_in_flight"],
                    cell.traffic["trace_chunks"]))
    require_tpu(cell.chips)

    from repro import compile_cache

    cache = compile_cache.enable()
    clock = cell_mod.CompileClock()
    cell_mod.log(f"perfbench: {cell.name} seed {args.seed}, compile cache "
                 f"{cache}")
    result = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                          clock, T_START)
    for line in check.report_lines(result["checks"]):
        cell_mod.log(line)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
